"""Full-sequence fused ΔGRU — one ``pallas_call`` per utterance/stream.

``delta_gru_cell`` images the ASIC's datapath for a single 16 ms frame, but
invoking it per timestep betrays the chip's actual win: DeltaKWS keeps x̂,
ĥ and the M accumulators resident in on-chip SRAM for the *whole* stream,
so a skipped delta skips the MAC **and** the weight read, and nothing
round-trips off-chip between frames.  This kernel is the TPU image of that
state-resident loop (DESIGN.md §3):

  * grid = (n_batch_tiles, T / block_t) — the time axis is the innermost
    grid dimension, executed sequentially on one core; each grid step
    advances ``block_t`` frames through an in-kernel ``fori_loop`` (the
    recurrence order is unchanged — the tile only amortizes per-step grid
    overhead and batches the x/h HBM transfers, an autotunable knob);
  * the five state buffers (h, x̂, ĥ, M_x, M_h) are *output* refs whose
    index map is constant along t, so Pallas keeps them revisited in VMEM
    across all grid steps (the accumulator pattern) and flushes them to
    HBM exactly once, as the final state;
  * the weights' index map is constant along the whole grid, so W_x/W_h
    are DMA'd HBM→VMEM once and stay resident — the SRAM image;
  * the per-frame hidden vectors stream back to HBM (block index
    advancing with t); the per-frame non-zero-delta counts are a
    (T, block_b) block revisited across the whole tile and flushed once.
    Slots sit on its lane dim, so every ``block_t`` is a legal tiling
    and a compiled ``block_b`` is B or a multiple of 128
    (``autotune.legal_block_b``).  The time axis is declared
    "arbitrary" (sequential — state is carried across it), the batch
    axis "parallel".

One kernel launch per sequence instead of T launches, zero HBM traffic
for state, and the op-count statistics the energy model needs are
accumulated on-device.  Weights that do NOT fit VMEM take the
block-sparse path instead (``core.delta_gru`` composes ``delta_matvec``'s
scalar-prefetch block mask per step — see DESIGN.md §2/§3).

The int variant runs every Δ·W contraction exactly on the MXU
(DESIGN.md §12), for a layer of either cell (``core.delta_gru.CELLS``:
the gate count G is a static parameter, and a ΔLSTM's cell state c rides
the state blocks and the carry, under the name ``delta_lstm_seq_int``).
Its VMEM plan (``int_vmem_plan``) chooses per layer how the weights are
held, counts them and the tile, and raises the scoped-VMEM limit where a
wide layer needs it:

  * ``f32_image`` wherever it fits: the int8 block (double-buffered by
    the pipeline) is converted ONCE (at grid step 0) into an f32-valued
    copy in persistent VMEM scratch, and each contraction runs as
    ``fixed_point.packed_int8_dot_pair`` — f32 matmuls over byte-plane-
    split deltas in blocks of ≤ 512 contraction rows;
  * ``int8_planes`` where that image does not fit (a ΔLSTM-1024's second
    layer): the int8 codes stay in HBM (``memory_space=ANY``), one DMA
    at grid step 0 holds them once in VMEM scratch, and each contraction
    runs as ``fixed_point.int8_plane_dot_pair`` — native int8 matmuls of
    three signed byte planes of the deltas, int32 accumulation.

Both are exact at any width; weights that fit neither way are refused.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.delta_gru import CELLS
from repro.kernels.autotune import LANES, validate_block_b, validate_divisor
from repro.kernels.gru_math import delta_branch, f32_dot, gru_gates
from repro.kernels.platform import resolve_interpret

# Grid axis 0 tiles independent slots; axis 1 carries state through time.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _slot_vmem_bytes(I: int, H: int, T: int, block_t: int, gates: int = 3,
                     carries_c: bool = False) -> int:
    """VMEM bytes one slot adds to a tile: every block double-buffered,
    4-byte words, lane dims padded to 128 — the default-tile budget.
    The state is h, x̂, ĥ, two G·H memories and, for a cell that
    carries it, c."""
    lanes = lambda d: -(-d // LANES) * LANES
    seq = block_t * (lanes(I) + lanes(H))                 # xs in, hs out
    state = 2 * (2 * lanes(H) + lanes(I) + 2 * lanes(gates * H)
                 + carries_c * lanes(H))                  # in + out
    return 2 * 4 * (seq + state + 2 * T)                  # + nz_dx/nz_dh


def _kernel(x_ref, h0_ref, xh0_ref, hh0_ref, mx0_ref, mh0_ref,
            wx_ref, wh_ref, th_ref,
            hs_ref, nzx_ref, nzh_ref,
            h_ref, xh_ref, hh_ref, mx_ref, mh_ref, *, hidden: int,
            block_t: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _load_state():
        # Fresh batch tile: seed the resident state buffers from the
        # caller's initial state (once per sequence, not per frame).
        h_ref[...] = h0_ref[...]
        xh_ref[...] = xh0_ref[...]
        hh_ref[...] = hh0_ref[...]
        mx_ref[...] = mx0_ref[...]
        mh_ref[...] = mh0_ref[...]

    th = th_ref[0, 0]
    row0 = t * block_t

    def step(k, carry):
        x = x_ref[k]
        h = h_ref[...]

        dx, new_xh, mx_mask = delta_branch(x, xh_ref[...], th)
        xh_ref[...] = new_xh
        dh, new_hh, mh_mask = delta_branch(h, hh_ref[...], th)
        hh_ref[...] = new_hh

        m_x = mx_ref[...] + f32_dot(dx, wx_ref[...])
        m_h = mh_ref[...] + f32_dot(dh, wh_ref[...])
        mx_ref[...] = m_x
        mh_ref[...] = m_h

        h_new = gru_gates(m_x, m_h, h, hidden)

        h_ref[...] = h_new
        hs_ref[k] = h_new
        nzx_ref[row0 + k, :] = jnp.sum(mx_mask, axis=-1).astype(jnp.int32)
        nzh_ref[row0 + k, :] = jnp.sum(mh_mask, axis=-1).astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, block_t, step, 0)


@functools.partial(jax.jit, static_argnames=("block_b", "block_t",
                                             "interpret"))
def delta_gru_seq(xs, h0, x_hat0, h_hat0, m_x0, m_h0, w_x, w_h, threshold,
                  *, block_b: int | None = None, block_t: int | None = None,
                  interpret: bool | None = None):
    """Run a ΔGRU over a whole sequence in ONE kernel invocation.

    Args:
      xs:      (T, B, I) inputs, one row per 16 ms frame.
      h0, x_hat0, h_hat0, m_x0, m_h0: initial delta state (see
        ``core.delta_gru.DeltaState``; m_x0 carries the bias).
      w_x: (I, 3H); w_h: (H, 3H); threshold: scalar Δ_TH.
      block_b: batch-tile size (``autotune.legal_block_b``; default
        B under the interpreter, the largest tile fitting the VMEM
        budget when compiled).
      block_t: frames per grid step (must divide T; default 1).  The
        frames still execute strictly sequentially inside the tile —
        bit-identical output, fewer grid steps.

    Returns ``(hs, (h, x_hat, h_hat, m_x, m_h), nz_dx, nz_dh)`` with
    hs (T, B, H) and nz_* (T, B) int32 per-frame transmit counts.
    """
    T, B, I = xs.shape
    H = h0.shape[1]
    # Shape discipline: block specs are derived from xs/h0, and a
    # mismatched operand would be silently padded by interpret mode —
    # corrupting resident state instead of erroring.
    assert h0.shape == h_hat0.shape == (B, H), (h0.shape, h_hat0.shape)
    assert x_hat0.shape == (B, I), (x_hat0.shape, (B, I))
    assert m_x0.shape == m_h0.shape == (B, 3 * H), (m_x0.shape, m_h0.shape)
    assert w_x.shape == (I, 3 * H), (w_x.shape, (I, 3 * H))
    assert w_h.shape == (H, 3 * H), (w_h.shape, (H, 3 * H))
    bt = validate_divisor("delta_gru_seq", "block_t", block_t, "T", T)
    bb = validate_block_b("delta_gru_seq", B, block_b, interpret=interpret,
                          slot_bytes=_slot_vmem_bytes(I, H, T, bt))
    n_b = B // bb

    f32 = lambda a: a.astype(jnp.float32)
    th = jnp.full((1, 1), threshold, jnp.float32)
    kernel = functools.partial(_kernel, hidden=H, block_t=bt)

    state_spec = lambda d: pl.BlockSpec((bb, d), lambda b, t: (b, 0))
    fixed_spec = lambda s: pl.BlockSpec(s, lambda b, t: tuple(
        0 for _ in s))
    seq_spec = lambda d: pl.BlockSpec((bt, bb, d), lambda b, t: (t, b, 0))

    out_shapes = (
        jax.ShapeDtypeStruct((T, B, H), jnp.float32),   # hs
        jax.ShapeDtypeStruct((T, B), jnp.int32),        # nz_dx
        jax.ShapeDtypeStruct((T, B), jnp.int32),        # nz_dh
        jax.ShapeDtypeStruct((B, H), jnp.float32),      # h
        jax.ShapeDtypeStruct((B, I), jnp.float32),      # x_hat
        jax.ShapeDtypeStruct((B, H), jnp.float32),      # h_hat
        jax.ShapeDtypeStruct((B, 3 * H), jnp.float32),  # m_x
        jax.ShapeDtypeStruct((B, 3 * H), jnp.float32),  # m_h
    )
    out_specs = (
        seq_spec(H),
        # Whole-sequence counts per tile, revisited along t.
        pl.BlockSpec((T, bb), lambda b, t: (0, b)),
        pl.BlockSpec((T, bb), lambda b, t: (0, b)),
        state_spec(H), state_spec(I), state_spec(H),
        state_spec(3 * H), state_spec(3 * H),
    )
    hs, nz_dx, nz_dh, h, x_hat, h_hat, m_x, m_h = pl.pallas_call(
        kernel,
        grid=(n_b, T // bt),
        in_specs=[
            seq_spec(I),
            state_spec(H), state_spec(I), state_spec(H),
            state_spec(3 * H), state_spec(3 * H),
            fixed_spec((I, 3 * H)), fixed_spec((H, 3 * H)),
            fixed_spec((1, 1)),
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        compiler_params=_COMPILER_PARAMS,
        name="delta_gru_seq",
        interpret=resolve_interpret(interpret),
    )(f32(xs), f32(h0), f32(x_hat0), f32(h_hat0), f32(m_x0), f32(m_h0),
      f32(w_x), f32(w_h), th)
    return hs, (h, x_hat, h_hat, m_x, m_h), nz_dx, nz_dh


# --------------------------------------------------------------- int variant
# The default scoped-VMEM limit of a Mosaic kernel on v5e, and the most a
# kernel may ask for: v5e has 128 MiB of VMEM per core; the rest is left
# to the compiler's own buffers.
SCOPED_VMEM_DEFAULT_BYTES = 16 * 2 ** 20
VMEM_CAP_BYTES = 100 * 2 ** 20

# How the int kernel holds a layer's weights in VMEM, in order of
# preference: the int8 block (double-buffered by the pipeline) beside a
# float32 image converted once, which the byte-plane f32 dot reads; or,
# where that does not fit, the int8 codes alone, DMA'd once from HBM
# into single-buffered scratch and read by the native int8 plane dot.
F32_IMAGE, INT8_PLANES = "f32_image", "int8_planes"


class VmemPlan(NamedTuple):
    """The int kernel's tiling and weight holding for one layer."""

    block_b: int          # slots per batch tile
    vmem_bytes: int       # what the kernel asks of VMEM
    weights: str          # F32_IMAGE or INT8_PLANES


def kernel_name(cell: str) -> str:
    """The int kernel's ``pallas_call`` name for a layer of ``cell``:
    ``delta_gru_seq_int``, ``delta_lstm_seq_int`` — one kernel body, a
    name per cell, so a device trace tells the cells apart."""
    return f"delta_{cell}_seq_int"


def int_vmem_plan(I: int, H: int, T: int, B: int, block_t: int,
                  block_b: int | None, interpret: bool | None = None, *,
                  cell: str = "gru") -> VmemPlan:
    """The int kernel's plan for one layer of ``cell``, from what it
    holds: the weights, and per slot the tile's blocks
    (``_slot_vmem_bytes``) plus the frame step's live temporaries.

    The weights are held as their float32 image (``F32_IMAGE``: 6 B a
    weight, the int8 block double-buffered and the image) wherever that
    whole plan fits ``VMEM_CAP_BYTES``, else as int8 codes held once
    (``INT8_PLANES``: 1 B a weight).  The tile is ``validate_block_b``'s
    rule with the held bytes counted against its budget.  Raises
    ``NotImplementedError`` when neither holding fits at the smallest
    legal tile: no kernel is built that Mosaic would refuse."""
    spec = CELLS[cell]
    GH = spec.gates * H
    tile = _slot_vmem_bytes(I, H, T, block_t, spec.gates, spec.carries_c)
    holdings = (
        # Fused M and its increment, both dots' two plane products, the
        # float32 planes and the gates' float32 intermediates.
        (F32_IMAGE, (2 + 4) * (I + H) * GH,
         4 * (4 * GH + 4 * GH + 2 * (I + H) + 4 * GH)),
        # Fused M and its increment, both dots' three plane products,
        # the int32 planes and the gates' float32 intermediates.
        (INT8_PLANES, (I + H) * GH,
         4 * (4 * GH + 6 * GH + 3 * (I + H) + 4 * GH)),
    )
    for weights, resident, temps in holdings:
        bb = validate_block_b(kernel_name(cell), B, block_b,
                              interpret=interpret, slot_bytes=tile,
                              fixed_bytes=resident)
        need = resident + bb * (tile + temps)
        if need <= VMEM_CAP_BYTES:
            return VmemPlan(bb, need, weights)
    raise NotImplementedError(
        f"{kernel_name(cell)}: the layer of I={I}, H={H} needs {need} B of "
        f"VMEM at block_b={bb} even with its weights held as int8 planes "
        f"({resident} B), above the {VMEM_CAP_BYTES} B a kernel may use; "
        f"the weights cannot stay resident and no int8 kernel streams them")


def int_kernel_tiles(cell: str, B: int, I: int, H: int, T: int, threshold,
                     block_b: int | None, block_t: int | None,
                     interpret: bool | None) -> tuple:
    """(block_b, block_t) the int kernel runs a layer with: the caller's
    knobs, else the ``kernels.autotune`` cache's for this shape, else
    ``None`` (the kernel's static defaults)."""
    if block_b is None or block_t is None:
        from repro.kernels import autotune
        tuned = autotune.resolve(kernel_name(cell), (B, I, H), "int8",
                                 threshold, interpret=interpret, B=B, T=T)
        block_b = block_b if block_b is not None else tuned.get("block_b")
        block_t = block_t if block_t is not None else tuned.get("block_t")
    return block_b, block_t


def _int_kernel(*refs, fmt, block_t: int, carries_c: bool, weights: str):
    """The int kernel body.  ``refs``: xs; the state blocks in (h, x̂, ĥ,
    m_x, m_h, and c for a cell that carries it); w_x, w_h, th; hs and
    the two transmit counts; the state blocks out, in the same order;
    then the scratch the weight holding needs."""
    from repro.core import fixed_point as fp

    n = 6 if carries_c else 5
    x_ref, state0 = refs[0], refs[1:1 + n]
    wx_ref, wh_ref, th_ref = refs[1 + n:4 + n]
    hs_ref, nzx_ref, nzh_ref = refs[4 + n:7 + n]
    state, scratch = refs[7 + n:7 + 2 * n], refs[7 + 2 * n:]
    h_ref, xh_ref, hh_ref, mx_ref, mh_ref = state[:5]

    t = pl.program_id(1)
    row0 = t * block_t

    @pl.when(t == 0)
    def _load_state():
        for dst, src in zip(state, state0):
            dst[...] = src[...]
        if fmt is None:
            return
        if weights == F32_IMAGE:
            # One-time weight conversion: the int8 image becomes an
            # f32-valued copy in persistent VMEM scratch, so every
            # frame's dot reads float operands (no per-frame cast).
            scratch[0][...] = wx_ref[...].astype(jnp.float32)
            scratch[1][...] = wh_ref[...].astype(jnp.float32)
        else:
            # One DMA of each int8 weight matrix from HBM, held once.
            copies = [pltpu.make_async_copy(src, dst, scratch[2].at[k])
                      for k, (src, dst) in enumerate(
                          ((wx_ref, scratch[0]), (wh_ref, scratch[1])))]
            for c in copies:
                c.start()
            for c in copies:
                c.wait()

    if fmt is None:
        dot, w_x, w_h = None, wx_ref[...], wh_ref[...]
    elif weights == F32_IMAGE:
        dot, w_x, w_h = fp.packed_int8_dot_pair, scratch[0][...], \
            scratch[1][...]
    else:
        dot, w_x, w_h = fp.int8_plane_dot_pair, scratch[0][...], \
            scratch[1][...]
    th_x, th_h = th_ref[0, 0], th_ref[0, 1]

    # State rides the fori_loop CARRY, not the refs: the refs are read
    # once per grid step and written back once after the inner loop.
    # Interpret mode charges every ref read/write as a real op, so at
    # block_t=4 this removes ~12 ops per frame versus the read-compute-
    # write-per-frame form — numerics untouched (same values, same
    # order; the int-mode casts in the frame step become no-ops because
    # the carry already holds int32).  The two accumulator halves ride
    # the carry FUSED as [m_x | m_h] — concatenated once here, split
    # once at writeback — matching the frame step's fused block.
    wide = (jnp.float32 if fmt is None else jnp.int32)
    half = mx_ref.shape[-1]

    def step(k, carry):
        if carries_c:
            h, xh, hh, m, c = carry
            h, c, xh, hh, m, mask_x, mask_h = fp.lstm_frame_step(
                fmt, x_ref[k], h, c, xh, hh, m, w_x, w_h, th_x, th_h,
                dot=dot)
            new = (h, xh, hh, m, c)
        else:
            h, xh, hh, m = carry
            h, xh, hh, m, mask_x, mask_h = fp.gru_frame_step(
                fmt, x_ref[k], h, xh, hh, m, w_x, w_h,
                th_x, th_h, dot=dot)
            new = (h, xh, hh, m)
        hs_ref[k] = h.astype(hs_ref.dtype)
        nzx_ref[row0 + k, :] = jnp.sum(mask_x, axis=-1).astype(jnp.int32)
        nzh_ref[row0 + k, :] = jnp.sum(mask_h, axis=-1).astype(jnp.int32)
        return new

    carry = jax.lax.fori_loop(
        0, block_t, step,
        (h_ref[...].astype(wide), xh_ref[...].astype(wide),
         hh_ref[...].astype(wide),
         jnp.concatenate([mx_ref[...], mh_ref[...]], axis=-1))
        + ((state[5][...].astype(wide),) if carries_c else ()))
    h, xh, hh, m = carry[:4]
    h_ref[...] = h.astype(h_ref.dtype)
    xh_ref[...] = xh.astype(xh_ref.dtype)
    hh_ref[...] = hh.astype(hh_ref.dtype)
    mx_ref[...] = m[:, :half].astype(mx_ref.dtype)
    mh_ref[...] = m[:, half:].astype(mh_ref.dtype)
    if carries_c:
        state[5][...] = carry[4].astype(state[5].dtype)


@functools.partial(jax.jit, static_argnames=("fmt", "cell", "block_b",
                                             "block_t", "interpret"))
def delta_gru_seq_int(xs, h0, x_hat0, h_hat0, m_x0, m_h0, w_x, w_h, th,
                      c0=None, *, fmt=None, cell: str = "gru",
                      block_b: int | None = None,
                      block_t: int | None = None,
                      interpret: bool | None = None):
    """The int8-weight/int16-state variant of the fused sequence kernel.

    Same sequence-resident structure as ``delta_gru_seq`` (grid =
    (n_batch_tiles, T / block_t), state buffers VMEM-revisited, weights
    resident), but the datapath is ``core.fixed_point.gru_frame_step``:

      * ``fmt`` a ``GruFormats`` — integer-code operands: xs/h/x̂/ĥ are
        int16 codes, m_x/m_h int32 on the 24-bit saturating accumulator
        grid, weights int8, ``th`` a (1, 2) int32 [th_x, th_h].  Every
        Δ·W runs exactly on the MXU, at any contraction length, against
        the weights as ``int_vmem_plan`` holds them: byte-plane f32 dots
        against a one-time f32 image in VMEM scratch (``fixed_point.
        packed_int8_dot_pair``), or, where that image does not fit,
        int8 plane dots against the int8 codes DMA'd once into scratch
        (``fixed_point.int8_plane_dot_pair``).  Bit-identical to the
        golden ``fixed_point.int_gru_scan`` scan either way.
      * ``fmt=None`` — identity-quant conformance mode: float operands
        (``th`` (1, 2) float32, both entries Δ_TH) through the SAME
        kernel skeleton, executing the float math in the float kernel's
        op order — bit-identical to ``delta_gru_seq`` and the XLA scan.
        This isolates the int kernel's plumbing (dispatch, block specs,
        state carry) from quantization in the differential fuzz suite.

    ``cell`` (``core.delta_gru.CELLS``) sets the gate count G (weights
    and memories G·H wide) and whether the cell state ``c0`` (int16
    codes, B × H) rides the state blocks and the carry: ``"lstm"`` runs
    ``fixed_point.lstm_frame_step`` under the name ``delta_lstm_seq_int``
    (``kernel_name``); integer codes only.

    ``block_b``/``block_t`` tile the batch/time grid axes (numerics-
    invariant, autotunable); the tile, the weight holding and the
    kernel's VMEM limit follow ``int_vmem_plan``.

    Returns ``(hs, DeltaState(h, x̂, ĥ, m_x, m_h), nz_dx, nz_dh)``, the
    state a ``DeltaLstmState`` with c for a cell that carries it.
    """
    from repro.core.delta_gru import DeltaLstmState, DeltaState
    spec = CELLS[cell]
    T, B, I = xs.shape
    H = h0.shape[1]
    GH = spec.gates * H
    assert h0.shape == h_hat0.shape == (B, H), (h0.shape, h_hat0.shape)
    assert x_hat0.shape == (B, I), (x_hat0.shape, (B, I))
    assert m_x0.shape == m_h0.shape == (B, GH), (m_x0.shape, m_h0.shape)
    assert w_x.shape == (I, GH), (w_x.shape, (I, GH))
    assert w_h.shape == (H, GH), (w_h.shape, (H, GH))
    assert th.shape == (1, 2), th.shape
    assert (c0 is not None) == spec.carries_c, (cell, c0 is None)
    assert c0 is None or (c0.shape == (B, H) and fmt is not None)
    name = kernel_name(cell)
    bt = validate_divisor(name, "block_t", block_t, "T", T)
    bb, vmem, weights = int_vmem_plan(I, H, T, B, bt, block_b, interpret,
                                      cell=cell)
    if fmt is None and weights != F32_IMAGE:
        raise NotImplementedError(
            f"{name}: the float conformance mode holds float weights; "
            f"I={I}, H={H} needs them held as int8 planes")
    compiler_params = _COMPILER_PARAMS
    if vmem > SCOPED_VMEM_DEFAULT_BYTES:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(vmem))

    if fmt is not None:
        # Widen the code stream once at dispatch, not once per frame:
        # the frame step computes on int32, so feeding int32 blocks
        # makes its per-frame x cast a no-op (values unchanged).
        xs = xs.astype(jnp.int32)
    kernel = functools.partial(_int_kernel, fmt=fmt, block_t=bt,
                               carries_c=spec.carries_c, weights=weights)
    state_spec = lambda d: pl.BlockSpec((bb, d), lambda b, t: (b, 0))
    fixed_spec = lambda s: pl.BlockSpec(s, lambda b, t: tuple(
        0 for _ in s))
    seq_spec = lambda d: pl.BlockSpec((bt, bb, d), lambda b, t: (t, b, 0))
    state0 = (h0, x_hat0, h_hat0, m_x0, m_h0) + ((c0,) if spec.carries_c
                                                 else ())
    widths = (H, I, H, GH, GH, H)[:len(state0)]
    if weights == F32_IMAGE:
        weight_specs = [fixed_spec((I, GH)), fixed_spec((H, GH))]
        scratch = ([pltpu.VMEM((I, GH), jnp.float32),
                    pltpu.VMEM((H, GH), jnp.float32)]
                   if fmt is not None else [])
    else:
        weight_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        scratch = [pltpu.VMEM((I, GH), jnp.int8),
                   pltpu.VMEM((H, GH), jnp.int8),
                   pltpu.SemaphoreType.DMA((2,))]

    out_shapes = (
        jax.ShapeDtypeStruct((T, B, H), h0.dtype),      # hs
        jax.ShapeDtypeStruct((T, B), jnp.int32),        # nz_dx
        jax.ShapeDtypeStruct((T, B), jnp.int32),        # nz_dh
    ) + tuple(jax.ShapeDtypeStruct((B, d), a.dtype)    # the state
              for d, a in zip(widths, state0))
    out_specs = (
        seq_spec(H),
        # Whole-sequence counts per tile, revisited along t.
        pl.BlockSpec((T, bb), lambda b, t: (0, b)),
        pl.BlockSpec((T, bb), lambda b, t: (0, b)),
    ) + tuple(state_spec(d) for d in widths)
    hs, nz_dx, nz_dh, *final = pl.pallas_call(
        kernel,
        grid=(B // bb, T // bt),
        in_specs=[seq_spec(I), *(state_spec(d) for d in widths),
                  *weight_specs, fixed_spec((1, 2))],
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=scratch,
        compiler_params=compiler_params,
        name=name,
        interpret=resolve_interpret(interpret),
    )(xs, *state0, w_x, w_h, th)
    kind = DeltaLstmState if spec.carries_c else DeltaState
    return hs, kind(*final), nz_dx, nz_dh
