"""Plain reference of the DeltaKWS model, from the paper and the config.

DeltaKWS (arXiv:2405.03905 §II): 12-bit 8 kHz audio → a bank of 4th-order
IIR band-pass filters (two DF2T biquads per channel, symmetric numerator
b1 = 0, b2 = −b0) → full-wave envelope with a one-pole low-pass → one
frame per 16 ms, log2-compressed onto the 12-bit feature grid → ΔGRU
(inputs and hidden state transmitted only when they moved by more than
Δ_TH; pre-activations accumulated in a persistent memory M) → FC.  The
always-on mode adds an energy VAD that holds the features while the
stream is silent, and a posterior-smoothing hysteresis head that turns
per-frame posteriors into keyword events.

Written from those equations and from the sizes and formats in the
configuration file, with ``jax.numpy`` and ``lax.scan`` only: it imports
nothing of the program and takes nothing the program made.  It designs
its own filter bank and quantizes the benchmark's float weights itself.
Every float contraction runs at HIGHEST precision.

Two numerics, as the configuration states:

  float32  float arithmetic throughout;
  int8     the IC's fixed-point datapath: 12-bit audio codes, 16-bit
           filter registers (products requantized with round-half-up
           shifts, saturating), int8 weights with per-tensor power-of-two
           scales, int16 hidden state, a 24-bit saturating accumulator,
           σ/tanh evaluated exactly on the dequantized pre-activation.

A configuration may state ``layers``, a stack of ΔGRU layers of width
``hidden`` (default one): the first reads the features, each layer above
reads the hidden state the layer below computed in the same frame, and
the FC head reads the top layer.  The weights' keys are
``bench/model.py``'s (``w_x, w_h, b``, then ``w_x_<l>, w_h_<l>, b_<l>``).
In int8 a layer above the first Δ-encodes the int16 hidden codes of the
layer below (``hid_frac`` fraction bits, its Δ threshold on that grid)
as its input; its input weights take a power-of-two scale of their own,
and its accumulator, saturation and gates are the first layer's.

A configuration may state ``cell`` (``bench/model.py``'s ``CELLS``):
``"gru"``, the default, or ``"lstm"``, a ΔLSTM (Spartus, arXiv:2108.02297)
whose layers carry a cell vector c beside h.  Its Δ-encoding and memories
are the ΔGRU's, G·H = 4H wide, gate-major i, f, g, o:

  float32  M = m_x + m_h;  i, f, o = σ(M);  g = tanh(M);
           c' = f⊙c + i⊙g;  h' = o⊙tanh(c').
  int8     i, f, o, g codes at ``hid_frac``, rounded from σ / tanh of
           M·2^−acc_frac as the ΔGRU's gates are; c an int16 code at
           ``formats["cell_frac"]``:
           c' = sat16(rshr(f·c, hid_frac)
                      + rshr(i·g, 2·hid_frac − cell_frac)),
           h' = sat16(rshr(o·round(tanh(c'·2^−cell_frac)·2^hid_frac),
                           hid_frac)),
           rshr the round-half-up arithmetic shift.

The check compares every layer's h, not c: through a slot's silence tail
an error in c keeps reaching h through o⊙tanh(c).

``precision`` selects the comparison's control: "high" computes every
float contraction in three bfloat16 passes (what the TPU's ``high``
precision does), and ``weight_bits=4`` rounds the int8 weights onto the
int4 grid.  Neither is ever what the configuration states.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import CELLS, cell, layer_keys, layers

NO_EVENT = -1
HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------ filter bank
def _mel(f):
    return 2595.0 * np.log10(1.0 + np.asarray(f, np.float64) / 700.0)


def _mel_inv(m):
    return 700.0 * (10.0 ** (np.asarray(m, np.float64) / 2595.0) - 1.0)


def _biquad_pair(f_lo: float, f_hi: float, fs: float) -> np.ndarray:
    """4th-order Butterworth band-pass as two biquads (2, 6) rows
    [b0 b1 b2 1 a1 a2]: prewarped LP→BP transform of the 2nd-order
    prototype, bilinear transform, conjugate pole pairs, zeros at ±1,
    unit gain at the geometric centre split evenly over the sections."""
    T = 1.0 / fs
    w1 = 2.0 / T * np.tan(np.pi * f_lo * T)
    w2 = 2.0 / T * np.tan(np.pi * f_hi * T)
    w0, bw = np.sqrt(w1 * w2), w2 - w1
    poles = []
    for p in (np.exp(1j * 3 * np.pi / 4), np.exp(1j * 5 * np.pi / 4)):
        disc = np.sqrt((p * bw) ** 2 / 4.0 - w0 ** 2 + 0j)
        poles += [p * bw / 2.0 + disc, p * bw / 2.0 - disc]
    k = 2.0 / T
    z = [(k + p) / (k - p) for p in poles]
    upper = sorted([p for p in z if p.imag >= 0], key=lambda p: p.real)
    lower = sorted([p for p in z if p.imag < 0], key=lambda p: p.real)
    if len(upper) == len(lower) == 2:
        pairs = [(upper[0], lower[0]), (upper[1], lower[1])]
    else:
        ps = sorted(z, key=lambda p: (p.real, p.imag))
        pairs = [(ps[0], ps[1]), (ps[2], ps[3])]
    sos = np.zeros((2, 6))
    for i, (p1, p2) in enumerate(pairs):
        sos[i] = [1.0, 0.0, -1.0, 1.0, -(p1 + p2).real, (p1 * p2).real]
    zc = np.exp(-2j * np.pi * np.sqrt(f_lo * f_hi) / fs)
    h = 1.0 + 0j
    for b0, b1, b2, _, a1, a2 in sos:
        h *= (b0 + b1 * zc + b2 * zc * zc) / (1.0 + a1 * zc + a2 * zc * zc)
    sos[:, :3] *= (1.0 / abs(h)) ** 0.5
    return sos


def _frac_bits(max_abs: float, total_bits: int) -> int:
    """Fraction bits of the two's-complement word that holds ``max_abs``."""
    int_bits = max(0, int(np.ceil(np.log2(max(max_abs, 1e-12) + 1e-12))))
    return max(0, total_bits - 1 - int_bits)


def filter_bank(fex: dict):
    """(coefficient rows (6, C) float64 [b0, a1, a2] × 2 sections,
    b fraction bits, a fraction bits) of the selected channels, with b
    and a quantized to their configured word widths."""
    fs = float(fex["sample_rate_hz"])
    centres = _mel_inv(np.linspace(_mel(fex["fmin_hz"]), _mel(fex["fmax_hz"]),
                                   fex["n_channels"]))
    m = _mel(centres)
    half = np.diff(m) / 2.0
    lo = _mel_inv(m - np.concatenate([[half[0]], half]))
    hi = _mel_inv(m + np.concatenate([half, [half[-1]]]))
    bank = np.stack([_biquad_pair(max(a, 20.0), min(b, fs / 2 - 20.0), fs)
                     for a, b in zip(lo, hi)])[list(fex["selection"])]
    b_frac = _frac_bits(float(np.max(np.abs(bank[..., :3]))), fex["b_bits"])
    a_frac = _frac_bits(float(np.max(np.abs(bank[..., 4:]))), fex["a_bits"])

    def q(v, frac, bits):
        step = 2.0 ** -frac
        top = 2.0 ** (bits - 1 - frac)
        return np.clip(np.round(v / step) * step, -top, top - step)

    bank[..., :3] = q(bank[..., :3], b_frac, fex["b_bits"])
    bank[..., 4:] = q(bank[..., 4:], a_frac, fex["a_bits"])
    # The integer datapath's coefficient words take their formats from
    # the quantized bank, the words that are stored.
    b_frac = _frac_bits(float(np.max(np.abs(bank[..., :3]))), fex["b_bits"])
    a_frac = _frac_bits(float(np.max(np.abs(bank[..., 4:]))), fex["a_bits"])
    rows = np.stack([bank[:, 0, 0], bank[:, 0, 4], bank[:, 0, 5],
                     bank[:, 1, 0], bank[:, 1, 4], bank[:, 1, 5]])
    return rows, b_frac, a_frac


# ------------------------------------------------------------- the model
class GruLayer(NamedTuple):
    """One recurrent layer's weights, of either cell: float32, or int8
    codes and int32 bias."""
    w_x: jax.Array           # (I, G·H): I = C for the first layer, H above
    w_h: jax.Array           # (H, G·H)
    b: jax.Array             # (G·H,)


class Model(NamedTuple):
    """Everything a block step needs, as device arrays and static ints."""
    coef: jax.Array          # (6, C) float32 rows, or int32 codes
    gru: tuple               # a GruLayer per layer, the first first
    w_fc: jax.Array          # (H, K)
    b_fc: jax.Array          # (K,)


@dataclasses.dataclass(frozen=True)
class Static:
    """Hashable constants of one configuration and control."""
    numerics: str
    precision: str
    frame_shift: int
    env_alpha: float
    log_eps: float
    threshold: float
    hidden: int
    cell: str = "gru"
    # int8 formats
    b_frac: int = 0
    a_frac: int = 0
    sig_frac: int = 13
    env_frac: int = 15
    alpha_frac: int = 15
    alpha_code: int = 0
    eps_code: int = 0
    feat_frac: int = 11
    hid_frac: int = 15
    cell_frac: int = 0       # the ΔLSTM's c
    acc_frac: int = 18
    acc_bits: int = 24
    shift_x: tuple = ()      # per layer
    shift_h: tuple = ()
    logit_frac: int = 0
    # always-on head
    vad_threshold: float = -1.0
    vad_hangover: int = 0
    smooth_alpha: float = 1.0
    fire_threshold: float = 1.0
    release_threshold: float = 1.0
    refractory: int = 0
    first_keyword: int = 0


def _exp2_scale(w) -> int:
    return int(np.ceil(np.log2(max(float(np.max(np.abs(w))), 1e-12))))


def _weight_codes(w, bits: int):
    """int8 codes (value = code · 2^(e-7)) and e; ``bits`` < 8 rounds
    onto the coarser grid of that width, kept on the int8 scale."""
    e = _exp2_scale(w)
    coarse = 1 << (8 - bits)
    lim = 1 << (bits - 1)
    c = np.clip(np.round(np.asarray(w, np.float64) / 2.0 ** e
                         * (lim)), -lim, lim - 1) * coarse
    return c.astype(np.int32), e


def build(config: dict, params: dict, *, precision: str = "highest",
          weight_bits: int | None = None) -> tuple[Model, Static]:
    """The reference model for ``config`` and the benchmark's float
    ``params`` (numpy or device arrays)."""
    fex = dict(config["fex"], sample_rate_hz=config["sample_rate_hz"])
    rows, b_frac, a_frac = filter_bank(fex)
    fs = float(config["sample_rate_hz"])
    alpha = float(1.0 - np.exp(-1.0 / (fs * fex["env_tau_s"])))
    det = config.get("detector", {})
    vad = config.get("vad", {"energy_threshold": -1.0, "hangover_frames": 0})
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    H = int(config["hidden"])
    keys = [layer_keys(layer) for layer in range(layers(config))]
    common = dict(
        precision=precision, frame_shift=int(fex["frame_shift"]),
        env_alpha=alpha, log_eps=float(fex["log_eps"]),
        threshold=float(config["delta_threshold"]), hidden=H,
        cell=cell(config),
        vad_threshold=float(vad["energy_threshold"]),
        vad_hangover=int(vad["hangover_frames"]),
        smooth_alpha=float(det.get("smooth_alpha", 1.0)),
        fire_threshold=float(det.get("fire_threshold", 1.0)),
        release_threshold=float(det.get("release_threshold", 1.0)),
        refractory=int(det.get("refractory_frames", 0)),
        first_keyword=int(det.get("first_keyword", 0)))
    if config["numerics"] == "float32":
        f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
        model = Model(coef=f32(rows),
                      gru=tuple(GruLayer(*(f32(p[k]) for k in ks))
                                for ks in keys),
                      w_fc=f32(p["w_fc"]), b_fc=f32(p["b_fc"]))
        return model, Static(numerics="float32", **common)
    f = config["formats"]
    bits = weight_bits or int(f["weight_bits"])
    acc_frac, acc_bits = int(f["acc_frac"]), int(f["acc_bits"])
    lim = 1 << (acc_bits - 1)
    gru, shift_x, shift_h = [], [], []
    for layer, (k_x, k_h, k_b) in enumerate(keys):
        in_frac = f["feat_frac"] if layer == 0 else f["hid_frac"]
        w_x, e_x = _weight_codes(p[k_x], bits)
        w_h, e_h = _weight_codes(p[k_h], bits)
        b = np.clip(np.round(p[k_b] * 2.0 ** acc_frac), -lim, lim - 1)
        gru.append(GruLayer(w_x=jnp.asarray(w_x), w_h=jnp.asarray(w_h),
                            b=jnp.asarray(b, jnp.int32)))
        shift_x.append(acc_frac - (in_frac + 7 - e_x))
        shift_h.append(acc_frac - (f["hid_frac"] + 7 - e_h))
    w_fc, e_fc = _weight_codes(p["w_fc"], bits)
    logit_frac = f["hid_frac"] + 7 - e_fc
    b_fc = np.clip(np.round(p["b_fc"] * 2.0 ** logit_frac),
                   -2 ** 31, 2 ** 31 - 1)
    coef = np.empty_like(rows)
    coef[[0, 3]] = np.round(rows[[0, 3]] * 2.0 ** b_frac)
    coef[[1, 2, 4, 5]] = np.round(rows[[1, 2, 4, 5]] * 2.0 ** a_frac)
    model = Model(coef=jnp.asarray(coef, jnp.int32), gru=tuple(gru),
                  w_fc=jnp.asarray(w_fc), b_fc=jnp.asarray(b_fc, jnp.int32))
    static = Static(
        numerics="int8", b_frac=b_frac, a_frac=a_frac,
        sig_frac=int(f["sig_frac"]), env_frac=int(f["env_frac"]),
        alpha_frac=int(f["alpha_frac"]),
        alpha_code=int(round(alpha * 2 ** f["alpha_frac"])),
        eps_code=int(round(fex["log_eps"] * 2 ** f["env_frac"])),
        feat_frac=int(f["feat_frac"]), hid_frac=int(f["hid_frac"]),
        cell_frac=(int(f["cell_frac"]) if CELLS[cell(config)].carries_c
                   else 0),
        acc_frac=acc_frac, acc_bits=acc_bits,
        shift_x=tuple(shift_x), shift_h=tuple(shift_h),
        logit_frac=logit_frac, **common)
    return model, static


# ------------------------------------------------------------- state
class State(NamedTuple):
    """The recurrent fields hold one array per layer, the first layer's
    first."""
    fex: jax.Array       # (B, 5, C) section-0, section-1 registers + env
    h: tuple             # (B, H)
    c: tuple             # (B, H) the ΔLSTM's cell state; () for a ΔGRU
    x_hat: tuple         # (B, I): I = C for the first layer, H above
    h_hat: tuple         # (B, H)
    m_x: tuple           # (B, G·H) input pre-activation memory (with bias)
    m_h: tuple           # (B, G·H) hidden pre-activation memory
    hold: jax.Array      # (B, C) VAD hold register
    hang: jax.Array      # (B,) VAD hangover
    smooth: jax.Array    # (B, K) smoothed posteriors
    active: jax.Array    # (B,) open event class or NO_EVENT
    refract: jax.Array   # (B,)


def init_state(model: Model, static: Static, batch: int) -> State:
    C = model.coef.shape[1]
    H, K = model.w_fc.shape
    spec = CELLS[static.cell]
    GH = spec.gates * H
    dt = jnp.float32 if static.numerics == "float32" else jnp.int32

    def zeros(*shape):
        return jnp.zeros((batch, *shape), dt)

    return State(
        fex=zeros(5, C), h=tuple(zeros(H) for _ in model.gru),
        c=tuple(zeros(H) for _ in model.gru) if spec.carries_c else (),
        x_hat=tuple(zeros(g.w_x.shape[0]) for g in model.gru),
        h_hat=tuple(zeros(H) for _ in model.gru),
        m_x=tuple(jnp.broadcast_to(g.b, (batch, GH)).astype(dt)
                  for g in model.gru),
        m_h=tuple(zeros(GH) for _ in model.gru), hold=zeros(C),
        hang=jnp.zeros((batch,), jnp.int32),
        smooth=jnp.zeros((batch, K), jnp.float32),
        active=jnp.full((batch,), NO_EVENT, jnp.int32),
        refract=jnp.zeros((batch,), jnp.int32))


# ------------------------------------------------------------ arithmetic
def _split_bf16(x):
    """x = hi + lo + rest with hi, lo on the bfloat16 grid.  Rounded with
    ``reduce_precision``: a bfloat16 round trip by ``astype`` may be
    simplified away by the compiler."""
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def fdot(a, b, precision: str):
    """float32 contraction: HIGHEST, or three bfloat16 passes ("high")."""
    if precision == "highest":
        return jnp.dot(a, b, precision=HIGHEST,
                       preferred_element_type=jnp.float32)
    a_hi, a_lo = _split_bf16(a)
    b_hi, b_lo = _split_bf16(b)
    d = functools.partial(jnp.dot, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    return d(a_lo, b_hi) + d(a_hi, b_lo) + d(a_hi, b_hi)


def _rshr(x, s: int):
    """Arithmetic right shift with round-half-up (s >= 1)."""
    return (x + (1 << (s - 1))) >> s


def _to_grid(x, shift: int):
    return x << shift if shift >= 0 else _rshr(x, -shift)


def _sat(x, bits: int):
    lim = 1 << (bits - 1)
    return jnp.clip(x, -lim, lim - 1)


def quantize_audio(x):
    """12-bit ADC: clip to [-1, 1 − 2^-11], round to the 2^-11 grid."""
    step = 2.0 ** -11
    x = jnp.clip(x.astype(jnp.float32), -1.0, 1.0 - step)
    return jnp.clip(jnp.round(x / step) * step, -1.0, 1.0 - step)


def _fex_sample(s, x, c, st: Static):
    """One audio sample through every channel's biquad pair + envelope.
    s: five (B, C) register rows; x: (B, 1); c: six (C,) coefficient rows."""
    b0a, a1a, a2a, b0b, a1b, a2b = c
    if st.numerics == "float32":
        # DF2T: y = b0·x + s1;  s1' = s2 − a1·y;  s2' = b2·x − a2·y,
        # with b1 = 0 and b2 = −b0.
        y0 = b0a * x + s[0]
        s0 = s[1] - a1a * y0
        s1 = -b0a * x - a2a * y0
        y1 = b0b * y0 + s[2]
        s2 = s[3] - a1b * y1
        s3 = -b0b * y0 - a2b * y1
        a = st.env_alpha
        env = (1.0 - a) * s[4] + a * jnp.abs(y1)
        return (s0, s1, s2, s3, env)
    bf, af = st.b_frac, st.a_frac
    x = x << (st.sig_frac - 11)                       # Q0.11 → Q2.13
    y0 = _sat(_rshr(b0a * x, bf) + s[0], 16)
    s0 = _sat(_rshr(-a1a * y0, af) + s[1], 16)
    s1 = _sat(_rshr(-b0a * x, bf) + _rshr(-a2a * y0, af), 16)
    y1 = _sat(_rshr(b0b * y0, bf) + s[2], 16)
    s2 = _sat(_rshr(-a1b * y1, af) + s[3], 16)
    s3 = _sat(_rshr(-b0b * y0, bf) + _rshr(-a2b * y1, af), 16)
    rect = _sat(jnp.abs(y1) << (st.env_frac - st.sig_frac), 16)
    one = 1 << st.alpha_frac
    env = _rshr((one - st.alpha_code) * s[4] + st.alpha_code * rect,
                st.alpha_frac)
    return (s0, s1, s2, s3, env)


def _compress(env, st: Static):
    """Envelope → 12-bit feature: (log2(env + ε) + 11) / 11 on [-1, 1)."""
    step = 2.0 ** -11
    if st.numerics == "float32":
        v = (jnp.log2(env + st.log_eps) + 11.0) / 11.0
        v = jnp.clip(v, -1.0, 1.0 - step)
        return jnp.clip(jnp.round(v / step) * step, -1.0, 1.0 - step)
    v = (jnp.log2((env + st.eps_code).astype(jnp.float32)
                  * float(2.0 ** -st.env_frac)) + 11.0) / 11.0
    v = jnp.clip(v, -1.0, 1.0 - step)
    return _sat(jnp.round(v * (1 << st.feat_frac)).astype(jnp.int32), 16)


def _delta_accumulate(x, h, x_hat, h_hat, m_x, m_h, g: GruLayer,
                      layer: int, st: Static):
    """Δ-encode x and h against the values last transmitted and add W·Δ
    into the memories M: the part every cell shares.  In int8 the first
    layer's x is a feature code, a higher layer's the hidden code of the
    layer below."""
    if st.numerics == "float32":
        th = st.threshold
        mx = jnp.abs(x - x_hat) > th
        dx = jnp.where(mx, x - x_hat, 0.0)
        x_hat = jnp.where(mx, x, x_hat)
        mh = jnp.abs(h - h_hat) > th
        dh = jnp.where(mh, h - h_hat, 0.0)
        h_hat = jnp.where(mh, h, h_hat)
        m_x = m_x + fdot(dx, g.w_x, st.precision)
        m_h = m_h + fdot(dh, g.w_h, st.precision)
    else:
        in_frac = st.feat_frac if layer == 0 else st.hid_frac
        th_x = int(np.floor(st.threshold * (1 << in_frac)))
        th_h = int(np.floor(st.threshold * (1 << st.hid_frac)))
        mx = jnp.abs(x - x_hat) > th_x
        dx = jnp.where(mx, x - x_hat, 0)
        x_hat = jnp.where(mx, x, x_hat)
        mh = jnp.abs(h - h_hat) > th_h
        dh = jnp.where(mh, h - h_hat, 0)
        h_hat = jnp.where(mh, h, h_hat)
        idot = functools.partial(jnp.dot, preferred_element_type=jnp.int32)
        m_x = _sat(m_x + _to_grid(idot(dx, g.w_x), st.shift_x[layer]),
                   st.acc_bits)
        m_h = _sat(m_h + _to_grid(idot(dh, g.w_h), st.shift_h[layer]),
                   st.acc_bits)
    nz = jnp.sum(mx, -1) + jnp.sum(mh, -1)
    return x_hat, h_hat, m_x, m_h, nz


def _gru_frame(x, h, x_hat, h_hat, m_x, m_h, g: GruLayer, layer: int,
               st: Static):
    """One ΔGRU layer's frame: Δ-encode x and h, accumulate W·Δ into M,
    gates r, u, c."""
    H = st.hidden
    x_hat, h_hat, m_x, m_h, nz = _delta_accumulate(
        x, h, x_hat, h_hat, m_x, m_h, g, layer, st)
    if st.numerics == "float32":
        r = jax.nn.sigmoid(m_x[:, :H] + m_h[:, :H])
        u = jax.nn.sigmoid(m_x[:, H:2 * H] + m_h[:, H:2 * H])
        c = jnp.tanh(m_x[:, 2 * H:] + r * m_h[:, 2 * H:])
        h = u * h + (1.0 - u) * c
    else:
        one = 1 << st.hid_frac
        acc = float(2.0 ** -st.acc_frac)
        ru = jnp.round(jax.nn.sigmoid(
            (m_x[:, :2 * H] + m_h[:, :2 * H]).astype(jnp.float32) * acc)
            * one).astype(jnp.int32)
        r, u = ru[:, :H], ru[:, H:]
        pre = (m_x[:, 2 * H:].astype(jnp.float32) * acc
               + (r.astype(jnp.float32) * float(1.0 / one))
               * (m_h[:, 2 * H:].astype(jnp.float32) * acc))
        c = jnp.round(jnp.tanh(pre) * one).astype(jnp.int32)
        h = _sat(_rshr(u * h + (one - u) * c, st.hid_frac), 16)
    return h, x_hat, h_hat, m_x, m_h, nz


def _lstm_frame(x, h, x_hat, h_hat, m_x, m_h, c, g: GruLayer, layer: int,
                st: Static):
    """One ΔLSTM layer's frame: the ΔGRU's Δ-encoding and accumulation,
    then gates i, f, g, o and the cell c (module docstring).  Returns the
    ΔGRU frame's six values and c'."""
    H = st.hidden
    x_hat, h_hat, m_x, m_h, nz = _delta_accumulate(
        x, h, x_hat, h_hat, m_x, m_h, g, layer, st)
    if st.numerics == "float32":
        m = m_x + m_h
        i = jax.nn.sigmoid(m[:, :H])
        f = jax.nn.sigmoid(m[:, H:2 * H])
        gg = jnp.tanh(m[:, 2 * H:3 * H])
        o = jax.nn.sigmoid(m[:, 3 * H:])
        c = f * c + i * gg
        h = o * jnp.tanh(c)
    else:
        one = 1 << st.hid_frac
        m = (m_x + m_h).astype(jnp.float32) * float(2.0 ** -st.acc_frac)

        def code(v):
            return jnp.round(v * one).astype(jnp.int32)

        i = code(jax.nn.sigmoid(m[:, :H]))
        f = code(jax.nn.sigmoid(m[:, H:2 * H]))
        gg = code(jnp.tanh(m[:, 2 * H:3 * H]))
        o = code(jax.nn.sigmoid(m[:, 3 * H:]))
        c = _sat(_rshr(f * c, st.hid_frac)
                 + _rshr(i * gg, 2 * st.hid_frac - st.cell_frac), 16)
        tc = code(jnp.tanh(c.astype(jnp.float32)
                           * float(2.0 ** -st.cell_frac)))
        h = _sat(_rshr(o * tc, st.hid_frac), 16)
    return h, x_hat, h_hat, m_x, m_h, nz, c


def _logits(h, model: Model, st: Static):
    """FC head → (votes, float logits).  The int8 path votes on its int32
    logit codes and dequantizes them for the posteriors."""
    if st.numerics == "float32":
        logits = fdot(h, model.w_fc, st.precision) + model.b_fc
        return jnp.argmax(logits, -1).astype(jnp.int32), logits
    codes = jnp.dot(h, model.w_fc, preferred_element_type=jnp.int32) \
        + model.b_fc
    return (jnp.argmax(codes, -1).astype(jnp.int32),
            codes.astype(jnp.float32) * float(2.0 ** -st.logit_frac))


def _detector(smooth, active, refract, logits, st: Static):
    """EMA-smoothed posteriors → one event per keyword (hysteresis)."""
    post = jax.nn.softmax(logits, axis=-1)
    smooth = smooth + st.smooth_alpha * (post - smooth)
    kw = smooth[:, st.first_keyword:]
    exceed = kw > st.fire_threshold
    cls = (jnp.argmax(jnp.where(exceed, kw, -jnp.inf), axis=-1)
           + st.first_keyword).astype(jnp.int32)
    idle = active == NO_EVENT
    fire = idle & (refract == 0) & jnp.any(exceed, axis=-1)
    release = (~idle) & jnp.all(kw < st.release_threshold, axis=-1)
    active = jnp.where(fire, cls, jnp.where(release, NO_EVENT, active))
    refract = jnp.where(fire, st.refractory, jnp.maximum(refract - 1, 0))
    return smooth, active, refract, jnp.where(fire, cls, NO_EVENT)


class Frames(NamedTuple):
    """Per-frame outputs of a block, frame-major (F, B)."""
    votes: jax.Array
    events: jax.Array
    gate: jax.Array
    nz: jax.Array


@functools.partial(jax.jit, static_argnames=("st", "detect"))
def block(model: Model, state: State, audio, *, st: Static, detect: bool):
    """Run (B, F·frame_shift) raw audio through the model, carrying
    ``state``; ``detect`` adds the VAD gate and the event head."""
    B, T = audio.shape
    F = T // st.frame_shift
    a = quantize_audio(audio[:, :F * st.frame_shift])
    frames = jnp.moveaxis(a.reshape(B, F, st.frame_shift), 1, 0)
    energy = jnp.mean(jnp.abs(frames), axis=-1)           # (F, B)
    samples = (frames if st.numerics == "float32"
               else jnp.round(frames * 2048.0).astype(jnp.int32))
    coef = [model.coef[i] for i in range(6)]

    def frame_step(s, inp):
        x_frame, e = inp
        regs = [s.fex[:, k] for k in range(5)]

        def sample_step(r, x):
            return list(_fex_sample(r, x[:, None], coef, st)), None

        regs, _ = jax.lax.scan(sample_step, regs, x_frame.T)
        x = _compress(regs[4], st)
        hold, hang = s.hold, s.hang
        gate = jnp.ones((B,), bool)
        if detect:
            speech = e > st.vad_threshold
            gate = speech | (hang > 0)
            hang = jnp.where(speech, st.vad_hangover,
                             jnp.maximum(hang - 1, 0)).astype(jnp.int32)
            x = jnp.where(gate[:, None], x, hold)
            hold = x
        gru, cells = [], []
        for layer, g in enumerate(model.gru):
            carry = (s.h[layer], s.x_hat[layer], s.h_hat[layer],
                     s.m_x[layer], s.m_h[layer])
            if st.cell == "lstm":
                *out, c = _lstm_frame(x, *carry, s.c[layer], g, layer, st)
                cells.append(c)
            else:
                out = _gru_frame(x, *carry, g, layer, st)
            gru.append(out)
            x = out[0]                    # the next layer's input
        h, x_hat, h_hat, m_x, m_h, nz = (tuple(f) for f in zip(*gru))
        votes, logits = _logits(h[-1], model, st)
        smooth, active, refract = s.smooth, s.active, s.refract
        events = jnp.full((B,), NO_EVENT, jnp.int32)
        if detect:
            smooth, active, refract, events = _detector(
                smooth, active, refract, logits, st)
        s = State(fex=jnp.stack(regs, axis=1), h=h, c=tuple(cells),
                  x_hat=x_hat, h_hat=h_hat, m_x=m_x, m_h=m_h, hold=hold,
                  hang=hang, smooth=smooth, active=active, refract=refract)
        return s, Frames(votes=votes, events=events, gate=gate,
                         nz=sum(nz).astype(jnp.int32))

    return jax.lax.scan(frame_step, state, (samples, energy))
