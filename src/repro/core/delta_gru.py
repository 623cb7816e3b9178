"""Delta-gated GRU (ΔGRU) — the paper's core contribution.

Implements the delta-network recurrence of Neil et al. (ICML'17) / Gao et al.
(FPGA'18) exactly as used by the DeltaKWS IC (Fig. 2/3):

A neuron transmits its activation only when the change since the *last
transmitted* value exceeds a threshold Δ_TH.  Define, per timestep t:

    Δx_t[i] = x_t[i] - x̂_{t-1}[i]      if |x_t[i] - x̂_{t-1}[i]| > Δ_TH else 0
    x̂_t[i]  = x_t[i]                    if transmitted, else x̂_{t-1}[i]
    (and identically for the hidden state h with memory ĥ)

The GRU pre-activations are then maintained *incrementally* in a persistent
accumulator M (the IC's "state buffer"):

    M_t = M_{t-1} + W_x Δx_t + W_h Δh_t

so that M_t == W_x x̂_t + W_h ĥ_t at all times.  A zero delta therefore skips
both the MAC *and* the weight-memory read for that column — the source of the
measured 3.4× energy / 2.4× latency reduction at 87% temporal sparsity.

This module provides:
  * ``delta_encode``         — the Δ encoder (threshold, memory update)
  * ``DeltaGRUCell``         — one timestep, returning op-count statistics
  * ``delta_gru_scan``       — full sequence via ``jax.lax.scan``
  * ``dense_gru_scan``       — reference dense GRU (identical params, Δ_TH=0
                               oracle and the paper's baseline)
  * parameter init/shape helpers.

GRU formulation (matches DeltaRNN / the IC: reset gate applied to the
candidate's *pre-activation*, a.k.a. the "type 2" / CuDNN variant, which is
what a delta accumulator requires — each of the three gates keeps its own
persistent pre-activation memory):

    r_t = σ(M_r)        M_r = W_xr x̂ + W_hr ĥ + b_r
    u_t = σ(M_u)        M_u = W_xu x̂ + W_hu ĥ + b_u
    c_t = tanh(W_xc x̂ + b_c + r_t ⊙ (W_hc ĥ))
    h_t = u_t ⊙ h_{t-1} + (1 - u_t) ⊙ c_t

All delta state (x̂, ĥ, M_r, M_u, M_xc, M_hc) is carried in the scan.

``CELLS`` is the one table of the recurrent cells a layer may be: the
ΔGRU above, and the ΔLSTM (Spartus, arXiv:2108.02297), whose four gates
i, f, g, o share the ΔGRU's Δ-encoding and memories (M is G·H wide) and
which carries a cell vector c beside h (``DeltaLstmState``).  Every
module that builds, carries or runs a layer reads its gate count and
whether it carries c from here.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.gru_math import f32_dot

Array = jax.Array


class DeltaGRUParams(NamedTuple):
    """Weights for a single ΔGRU layer (input dim I, hidden dim H)."""

    w_x: Array  # (I, 3H)  -> [r | u | c] input kernels
    w_h: Array  # (H, 3H)  -> [r | u | c] hidden kernels
    b: Array    # (3H,)


class Cell(NamedTuple):
    """What a recurrent cell is to the code that carries it."""

    gates: int          # G: blocks of H in a layer's pre-activations
    carries_c: bool     # a cell vector c carried beside h


CELLS = {"gru": Cell(gates=3, carries_c=False),
         "lstm": Cell(gates=4, carries_c=True)}


class DeltaState(NamedTuple):
    """Carried state of the delta recurrence (G = the cell's gates:
    3 for the ΔGRU, 4 for the ΔLSTM)."""

    h: Array       # (B, H)  hidden state
    x_hat: Array   # (B, I)  last transmitted input
    h_hat: Array   # (B, H)  last transmitted hidden
    m_x: Array     # (B, G·H) accumulated input pre-activations (incl. bias)
    m_h: Array     # (B, G·H) accumulated hidden pre-activations


class DeltaLstmState(NamedTuple):
    """``DeltaState`` of a cell that carries c: the same fields, then c."""

    h: Array       # (B, H)
    x_hat: Array   # (B, I)
    h_hat: Array   # (B, H)
    m_x: Array     # (B, 4H)
    m_h: Array     # (B, 4H)
    c: Array       # (B, H)  cell state


class DeltaStats(NamedTuple):
    """Per-step op statistics (all shapes (B,) unless noted)."""

    nz_dx: Array     # number of non-zero input deltas
    nz_dh: Array     # number of non-zero hidden deltas
    macs: Array      # MACs actually executed this step
    macs_dense: Array  # MACs a dense GRU would execute
    sram_reads: Array  # weight words read (== skipped-column-aware)


def init_delta_gru(key: Array, input_dim: int, hidden_dim: int,
                   dtype=jnp.float32, gates: int = 3) -> DeltaGRUParams:
    """One layer's weights, ``gates`` blocks of H wide (``CELLS``)."""
    k1, k2 = jax.random.split(key)
    GH = gates * hidden_dim
    # Orthogonal-ish recurrent init, scaled glorot for input kernels.
    w_x = jax.random.normal(k1, (input_dim, GH), dtype) * (
        1.0 / np.sqrt(input_dim))
    w_h = jax.random.normal(k2, (hidden_dim, GH), dtype) * (
        1.0 / np.sqrt(hidden_dim))
    b = jnp.zeros((GH,), dtype)
    return DeltaGRUParams(w_x, w_h, b)


def init_delta_state(batch: int, input_dim: int, hidden_dim: int,
                     params: DeltaGRUParams, dtype=jnp.float32) -> DeltaState:
    """Zero state.  m_x starts at the bias so M == W x̂ + W ĥ + b holds."""
    return DeltaState(
        h=jnp.zeros((batch, hidden_dim), dtype),
        x_hat=jnp.zeros((batch, input_dim), dtype),
        h_hat=jnp.zeros((batch, hidden_dim), dtype),
        m_x=jnp.broadcast_to(params.b.astype(dtype), (batch, 3 * hidden_dim)),
        m_h=jnp.zeros((batch, 3 * hidden_dim), dtype),
    )


def delta_encode(x: Array, x_hat: Array, threshold: Array | float):
    """Δ encoder: returns (delta, new_x_hat, transmitted_mask).

    delta[i] = x[i] - x_hat[i] where |x - x_hat| > th, else 0.
    x_hat only advances for transmitted components (the IC's Δ-encoder
    semantics — *not* an unconditional update, which would let small drifts
    accumulate unseen).
    """
    from repro.kernels.gru_math import delta_branch
    return delta_branch(x, x_hat, threshold)


@dataclasses.dataclass(frozen=True)
class DeltaGRUCell:
    """One ΔGRU timestep.  threshold=0 reproduces the dense GRU exactly.

    ``h_qformat`` (a ``core.quantize.QFormat``) snaps the hidden state to
    a fixed-point grid after the gates with a straight-through gradient —
    the QAT image of the IC's quantized ĥ memory (Q0.15 in the integer
    serving path): training then sees the same delta-threshold compares
    the deployed integer datapath performs.
    """

    hidden_dim: int
    threshold: float = 0.0
    h_qformat: Any = None

    def __call__(self, params: DeltaGRUParams, state: DeltaState, x: Array
                 ) -> tuple[DeltaState, Array, DeltaStats]:
        H = self.hidden_dim
        th = jnp.asarray(self.threshold, x.dtype)

        dx, x_hat, mx = delta_encode(x, state.x_hat, th)
        dh, h_hat, mh = delta_encode(state.h, state.h_hat, th)

        # Incremental pre-activation update: only non-zero delta columns
        # contribute.  Dense matmul of a sparse vector — numerically identical
        # to gathering the non-zero columns (what the IC / Pallas kernel do).
        m_x = state.m_x + f32_dot(dx, params.w_x)          # (B, 3H)
        m_h = state.m_h + f32_dot(dh, params.w_h)          # (B, 3H)
        h = _gru_gates(m_x, m_h, state.h, H)
        if self.h_qformat is not None:
            from repro.core.quantize import ste_quantize
            h = ste_quantize(h, self.h_qformat)

        # sram_reads == macs: one weight word per MAC (16b word = 2×8b wts
        # in the IC; accounted in the energy model).
        stats = _stats_from_counts(jnp.sum(mx, axis=-1),
                                   jnp.sum(mh, axis=-1), x.shape[-1], H)
        new_state = DeltaState(h=h, x_hat=x_hat, h_hat=h_hat, m_x=m_x, m_h=m_h)
        return new_state, h, stats


# VMEM budget for the sequence-resident Pallas kernel: beyond this the
# weights cannot stay resident and the block-sparse path takes over.
_SEQ_KERNEL_VMEM_BUDGET_BYTES = 8 * 2 ** 20


def _auto_block(n: int, target: int = 128) -> int:
    """Largest divisor of ``n`` that is <= target (static Python int)."""
    for d in range(min(n, target), 0, -1):
        if n % d == 0:
            return d
    return 1


def _stats_from_counts(nz_dx: Array, nz_dh: Array, in_dim: int,
                       hidden_dim: int) -> DeltaStats:
    """Rebuild DeltaStats from per-frame transmit counts (device-side)."""
    macs = (nz_dx + nz_dh) * (3 * hidden_dim)
    macs_dense = jnp.full_like(macs, (in_dim + hidden_dim) * 3 * hidden_dim)
    return DeltaStats(nz_dx=nz_dx, nz_dh=nz_dh, macs=macs,
                      macs_dense=macs_dense, sram_reads=macs)


def _gru_gates(m_x: Array, m_h: Array, h: Array, hidden_dim: int) -> Array:
    """The type-2 GRU nonlinearity (single source: kernels/gru_math.py)."""
    from repro.kernels.gru_math import gru_gates
    return gru_gates(m_x, m_h, h, hidden_dim)


def _delta_gru_scan_blocked(params: DeltaGRUParams, xs: Array,
                            threshold: float, state: DeltaState,
                            block_i: int | None, block_o: int | None,
                            interpret: bool | None,
                            ) -> tuple[Array, DeltaState, DeltaStats]:
    """Scan composing the block-sparse ``delta_matvec`` kernel per step.

    For models whose weights exceed the sequence kernel's VMEM budget:
    each step derives a block-activity mask from the thresholded deltas
    and skips the HBM→VMEM weight-tile fetch (and the MAC) for inactive
    blocks — the DESIGN.md §2 re-blocking applied inside the recurrence.
    """
    from repro.kernels.delta_matvec import delta_matvec, make_block_mask

    T, B, I = xs.shape
    H = params.w_h.shape[0]
    # block_i describes the INPUT axis; it only carries over to the
    # hidden-state matvec when it also divides H (delta_matvec requires
    # exact tiling) — otherwise each axis picks its own divisor.
    bi_x = block_i if block_i and I % block_i == 0 else _auto_block(I)
    bi_h = block_i if block_i and H % block_i == 0 else _auto_block(H)
    bo = block_o if block_o and (3 * H) % block_o == 0 else _auto_block(3 * H)
    th = jnp.asarray(threshold, xs.dtype)

    def body(carry: DeltaState, x):
        dx, x_hat, mx_mask = delta_encode(x, carry.x_hat, th)
        dh, h_hat, mh_mask = delta_encode(carry.h, carry.h_hat, th)
        m_x = delta_matvec(dx, params.w_x, carry.m_x,
                           make_block_mask(dx, bi_x),
                           block_i=bi_x, block_o=bo, interpret=interpret)
        m_h = delta_matvec(dh, params.w_h, carry.m_h,
                           make_block_mask(dh, bi_h),
                           block_i=bi_h, block_o=bo, interpret=interpret)
        h = _gru_gates(m_x, m_h, carry.h, H)
        stats = _stats_from_counts(jnp.sum(mx_mask, axis=-1),
                                   jnp.sum(mh_mask, axis=-1), I, H)
        new_state = DeltaState(h=h, x_hat=x_hat, h_hat=h_hat,
                               m_x=m_x, m_h=m_h)
        return new_state, (h, stats)

    final_state, (hs, stats) = jax.lax.scan(body, state, xs)
    return hs, final_state, stats


@jax.named_scope("kws.gru")
def delta_gru_scan(params: DeltaGRUParams, xs: Array, threshold: float = 0.0,
                   state: DeltaState | None = None, *,
                   backend: str = "xla", interpret: bool | None = None,
                   block_b: int | None = None, block_t: int | None = None,
                   block_i: int | None = None,
                   block_o: int | None = None, h_qformat=None,
                   event_driven: bool = False,
                   vmem_budget_bytes: int = _SEQ_KERNEL_VMEM_BUDGET_BYTES,
                   ) -> tuple[Array, DeltaState, DeltaStats]:
    """Run a ΔGRU over ``xs`` of shape (T, B, I).

    Args:
      params: ``DeltaGRUParams`` (w_x (I, 3H), w_h (H, 3H), b (3H,)).
      xs: (T, B, I) frame-major inputs.
      threshold: Δ_TH — the transmit deadband (0.0 = dense GRU exactly).
      state: carried ``DeltaState`` (None = fresh stream: zero x̂/ĥ/h,
        M seeded with the bias so M == W_x x̂ + W_h ĥ + b holds).
      backend: implementation selector, identical numerics —
        * ``"xla"``    — ``jax.lax.scan`` over ``DeltaGRUCell`` (default;
          differentiable — the training path).
        * ``"pallas"`` — ONE fused ``pallas_call`` for the whole sequence
          with weights and delta state VMEM-resident across grid steps
          (``kernels.delta_gru_seq``); falls back to a per-step
          composition of the block-sparse ``delta_matvec`` kernel when
          the weights exceed ``vmem_budget_bytes``.
        * ``"pallas-int"`` — the integer kernel's skeleton in its
          identity-quant conformance mode (float math, same op order):
          bit-identical to both paths above, exercising the int kernel's
          dispatch/plumbing.  The REAL integer datapath (int8 weights,
          int16 state, code-domain I/O) is
          ``core.fixed_point.int_gru_scan`` on a promoted
          ``IntGruWeights`` — it has its own entry point because its
          state and I/O live on integer grids.
      interpret: force the Pallas interpreter on/off (None = platform
        default).
      block_b / block_t / block_i / block_o: Pallas tile-size overrides
        (batch tile, time tile, input-block, output-block).  ``None``
        consults the ``kernels.autotune`` cache for this (kernel, shape,
        dtype, threshold-bucket, platform) and otherwise keeps the static
        defaults — behavior is unchanged until a cache is tuned.  All are
        numerics-invariant.
      h_qformat: QAT hidden-state quantization grid (XLA backend only —
        see ``DeltaGRUCell``).
      event_driven: active-slot compaction (``kernels.compaction``,
        DESIGN.md §13): slots whose whole chunk sits inside the Δ dead
        zone of their carried x̂ AND whose state is a proven bitwise
        fixed point are skipped; the remaining slots run compacted
        through the selected backend.  Bit-identical to the dense path
        by construction, faster at high temporal sparsity.  Host-level
        (dynamic shapes), so it cannot be called under ``jax.jit`` and
        returns host numpy arrays; incompatible with ``h_qformat``.
      vmem_budget_bytes: weight budget above which "pallas" takes the
        block-sparse per-step fallback.

    Returns:
      (hs (T, B, H), final ``DeltaState``, per-step ``DeltaStats``
      stacked over T).

    State contract: the returned state makes chunking bit-invisible —
    scanning [a|b] with the state carried equals one scan of the
    concatenation, on every backend.  The XLA path is differentiable:
    the delta threshold acts as a piecewise-constant gate; gradients
    flow through the transmitted path (straight-through on the gate),
    matching how DeltaRNN networks are trained.  The Pallas paths are
    inference/serving hot paths.
    """
    T, B, I = xs.shape
    H = params.w_h.shape[0]
    if state is None:
        state = init_delta_state(B, I, H, params, xs.dtype)
    if h_qformat is not None and backend != "xla":
        raise ValueError("h_qformat (QAT) requires the differentiable "
                         f"'xla' backend, got {backend!r}")

    if event_driven:
        if h_qformat is not None:
            raise ValueError("event_driven compaction is an inference "
                             "mode — incompatible with QAT (h_qformat)")
        from repro.kernels import compaction

        def run(xs_c, st):
            hs, fin, stats = delta_gru_scan(
                params, jnp.asarray(xs_c), threshold,
                DeltaState(*[jnp.asarray(s) for s in st]),
                backend=backend, interpret=interpret, block_i=block_i,
                block_o=block_o, vmem_budget_bytes=vmem_budget_bytes)
            return hs, tuple(fin), stats.nz_dx, stats.nz_dh

        held = compaction.held_slots(xs, state.x_hat, threshold)
        hs, st, nz_dx, nz_dh, _ = compaction.event_driven_seq(
            run, xs, tuple(state), held)
        return (jnp.asarray(hs), DeltaState(*[jnp.asarray(s) for s in st]),
                _stats_from_counts(jnp.asarray(nz_dx), jnp.asarray(nz_dh),
                                   I, H))

    if backend == "pallas-int":
        from repro.kernels import autotune
        from repro.kernels.delta_gru_seq import delta_gru_seq_int
        if block_b is None or block_t is None:
            tuned = autotune.resolve("delta_gru_seq_int", (B, I, H),
                                     "float32", threshold,
                                     interpret=interpret, B=B, T=T)
            block_b = block_b if block_b is not None else tuned.get("block_b")
            block_t = block_t if block_t is not None else tuned.get("block_t")
        f32 = lambda a: a.astype(jnp.float32)
        th = jnp.full((1, 2), threshold, jnp.float32)
        hs, final, nz_dx, nz_dh = delta_gru_seq_int(
            f32(xs), f32(state.h), f32(state.x_hat), f32(state.h_hat),
            f32(state.m_x), f32(state.m_h), f32(params.w_x),
            f32(params.w_h), th, fmt=None, block_b=block_b,
            block_t=block_t, interpret=interpret)
        return hs, final, _stats_from_counts(nz_dx, nz_dh, I, H)

    if backend == "pallas":
        weight_bytes = (I + H) * 3 * H * 4
        if weight_bytes > vmem_budget_bytes:
            return _delta_gru_scan_blocked(params, xs, threshold, state,
                                           block_i, block_o, interpret)
        from repro.kernels import autotune
        from repro.kernels.delta_gru_seq import delta_gru_seq
        if block_b is None or block_t is None:
            tuned = autotune.resolve("delta_gru_seq", (B, I, H), "float32",
                                     threshold, interpret=interpret,
                                     B=B, T=T)
            block_b = block_b if block_b is not None else tuned.get("block_b")
            block_t = block_t if block_t is not None else tuned.get("block_t")
        hs, final, nz_dx, nz_dh = delta_gru_seq(
            xs, state.h, state.x_hat, state.h_hat, state.m_x, state.m_h,
            params.w_x, params.w_h, threshold,
            block_b=block_b, block_t=block_t, interpret=interpret)
        return hs, DeltaState(*final), _stats_from_counts(nz_dx, nz_dh, I, H)
    if backend != "xla":
        raise ValueError(f"unknown ΔGRU backend: {backend!r}")

    cell = DeltaGRUCell(hidden_dim=H, threshold=threshold,
                        h_qformat=h_qformat)

    def body(carry, x):
        new_state, h, stats = cell(params, carry, x)
        return new_state, (h, stats)

    final_state, (hs, stats) = jax.lax.scan(body, state, xs)
    return hs, final_state, stats


def masked_delta_gru_scan(params: DeltaGRUParams, xs: Array,
                          threshold: float, state: DeltaState,
                          awake: Array
                          ) -> tuple[Array, DeltaState, DeltaStats]:
    """Wake-gated ΔGRU scan: the stage-1 half of the cascade (DESIGN.md
    §13).  ``awake`` is a (T, B) bool trace from the stage-0 wake gate;
    frames where a slot is asleep leave its ENTIRE delta state (h, x̂,
    ĥ, M) bit-frozen, emit the frozen h, and count ZERO executed MACs —
    the IC clock-gates the big recurrence, it does not run it and throw
    the result away.  Awake frames step through the same ``DeltaGRUCell``
    the dense XLA backend scans, so a trace that is awake everywhere is
    bit-identical to ``delta_gru_scan(backend="xla")`` (and through the
    locked kernel-conformance suite, to every other backend).

    Jit-compatible (static shapes): the freeze is a per-frame masked
    select, which is how a frame-granular gate can live inside the fused
    serving step.  ``macs_dense`` stays unmasked — the dense reference
    the duty cycle and sparsity are measured against runs every frame.
    """
    H = params.w_h.shape[0]
    cell = DeltaGRUCell(hidden_dim=H, threshold=threshold)

    def body(carry: DeltaState, inp):
        x, awk = inp
        new_state, _, stats = cell(params, carry, x)
        m = awk[:, None]
        carry = DeltaState(*(jnp.where(m, n, o)
                             for n, o in zip(new_state, carry)))
        z = jnp.zeros((), stats.nz_dx.dtype)
        stats = DeltaStats(
            nz_dx=jnp.where(awk, stats.nz_dx, z),
            nz_dh=jnp.where(awk, stats.nz_dh, z),
            macs=jnp.where(awk, stats.macs, z),
            macs_dense=stats.macs_dense,
            sram_reads=jnp.where(awk, stats.sram_reads, z))
        return carry, (carry.h, stats)

    final_state, (hs, stats) = jax.lax.scan(body, state, (xs, awake))
    return hs, final_state, stats


def dense_gru_scan(params: DeltaGRUParams, xs: Array,
                   h0: Array | None = None) -> Array:
    """Reference dense GRU (identical math to ΔGRU at threshold=0)."""
    T, B, I = xs.shape
    H = params.w_h.shape[0]
    if h0 is None:
        h0 = jnp.zeros((B, H), xs.dtype)

    def body(h, x):
        zx = f32_dot(x, params.w_x) + params.b
        zh = f32_dot(h, params.w_h)
        h = _gru_gates(zx, zh, h, H)
        return h, h

    _, hs = jax.lax.scan(body, h0, xs)
    return hs


def temporal_sparsity(stats: DeltaStats) -> Array:
    """Fraction of dense MACs skipped, averaged over time and batch."""
    return 1.0 - jnp.sum(stats.macs) / jnp.sum(stats.macs_dense)
