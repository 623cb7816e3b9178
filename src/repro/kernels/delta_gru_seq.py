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

The int variant runs every Δ·W contraction exactly on the float MXU
path (DESIGN.md §12): the int8 weight image is converted ONCE (at grid
step 0) into an f32-valued copy held in persistent VMEM scratch, and
each contraction runs as ``fixed_point.packed_int8_dot_pair`` — f32
matmuls over byte-plane-split deltas in blocks of ≤ 512 contraction
rows, exact at any width.  Its VMEM plan (``int_vmem_plan``) counts the
weights, their image and the tile, raises the scoped-VMEM limit where a
768-wide layer needs it, and refuses weights that cannot stay resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.autotune import LANES, validate_block_b, validate_divisor
from repro.kernels.gru_math import delta_branch, f32_dot, gru_gates
from repro.kernels.platform import resolve_interpret

# Grid axis 0 tiles independent slots; axis 1 carries state through time.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _slot_vmem_bytes(I: int, H: int, T: int, block_t: int) -> int:
    """VMEM bytes one slot adds to a tile: every block double-buffered,
    4-byte words, lane dims padded to 128 — the default-tile budget."""
    lanes = lambda d: -(-d // LANES) * LANES
    seq = block_t * (lanes(I) + lanes(H))                 # xs in, hs out
    state = 2 * (2 * lanes(H) + lanes(I) + 2 * lanes(3 * H))  # in + out
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


def int_vmem_plan(I: int, H: int, T: int, B: int, block_t: int,
                  block_b: int | None,
                  interpret: bool | None = None) -> tuple[int, int]:
    """(batch tile, VMEM bytes) of the int kernel, from what it holds:
    the int8 weights (double-buffered by the pipeline) and their float32
    image, and per slot the tile's blocks (``_slot_vmem_bytes``) plus
    the frame step's live temporaries.

    The tile is ``validate_block_b``'s rule with the resident bytes
    counted against its budget.  Raises ``NotImplementedError`` when even
    the smallest legal tile cannot hold its weights resident under
    ``VMEM_CAP_BYTES``: no kernel is built that Mosaic would refuse."""
    resident = (2 + 4) * (I + H) * 3 * H
    tile = _slot_vmem_bytes(I, H, T, block_t)
    bb = validate_block_b("delta_gru_seq_int", B, block_b,
                          interpret=interpret, slot_bytes=tile,
                          fixed_bytes=resident)
    # Fused M and its increment, both dots' two plane products, the
    # float32 planes and the gates' float32 intermediates.
    temps = 4 * (12 * H + 12 * H + 2 * (I + H) + 12 * H)
    need = resident + bb * (tile + temps)
    if need > VMEM_CAP_BYTES:
        raise NotImplementedError(
            f"delta_gru_seq_int: I={I}, H={H} needs {need} B of VMEM at "
            f"block_b={bb} ({resident} B of resident weights), above the "
            f"{VMEM_CAP_BYTES} B a kernel may use; the weights cannot stay "
            f"resident and no int8 kernel streams them")
    return bb, need


def _int_kernel(x_ref, h0_ref, xh0_ref, hh0_ref, mx0_ref, mh0_ref,
                wx_ref, wh_ref, th_ref,
                hs_ref, nzx_ref, nzh_ref,
                h_ref, xh_ref, hh_ref, mx_ref, mh_ref,
                wxf_ref=None, whf_ref=None, *, fmt, block_t: int):
    from repro.core import fixed_point as fp

    t = pl.program_id(1)
    row0 = t * block_t

    @pl.when(t == 0)
    def _load_state():
        h_ref[...] = h0_ref[...]
        xh_ref[...] = xh0_ref[...]
        hh_ref[...] = hh0_ref[...]
        mx_ref[...] = mx0_ref[...]
        mh_ref[...] = mh0_ref[...]
        if fmt is not None:
            # One-time weight conversion: the int8 image becomes an
            # f32-valued copy in persistent VMEM scratch, so every
            # frame's dot reads float operands (no per-frame cast).
            wxf_ref[...] = wx_ref[...].astype(jnp.float32)
            whf_ref[...] = wh_ref[...].astype(jnp.float32)

    if fmt is None:
        dot, w_x, w_h = None, wx_ref[...], wh_ref[...]
    else:
        dot, w_x, w_h = fp.packed_int8_dot_pair, wxf_ref[...], whf_ref[...]
    th_x, th_h = th_ref[0, 0], th_ref[0, 1]

    # State rides the fori_loop CARRY, not the refs: the refs are read
    # once per grid step and written back once after the inner loop.
    # Interpret mode charges every ref read/write as a real op, so at
    # block_t=4 this removes ~12 ops per frame versus the read-compute-
    # write-per-frame form — numerics untouched (same values, same
    # order; the int-mode casts in gru_frame_step become no-ops because
    # the carry already holds int32).  The two accumulator halves ride
    # the carry FUSED as [m_x | m_h] — concatenated once here, split
    # once at writeback — matching the frame step's fused block.
    wide = (jnp.float32 if fmt is None else jnp.int32)
    half = mx_ref.shape[-1]

    def step(k, carry):
        h, xh, hh, m = carry
        h, xh, hh, m, mask_x, mask_h = fp.gru_frame_step(
            fmt, x_ref[k], h, xh, hh, m, w_x, w_h,
            th_x, th_h, dot=dot)
        hs_ref[k] = h.astype(hs_ref.dtype)
        nzx_ref[row0 + k, :] = jnp.sum(mask_x, axis=-1).astype(jnp.int32)
        nzh_ref[row0 + k, :] = jnp.sum(mask_h, axis=-1).astype(jnp.int32)
        return h, xh, hh, m

    h, xh, hh, m = jax.lax.fori_loop(
        0, block_t, step,
        (h_ref[...].astype(wide), xh_ref[...].astype(wide),
         hh_ref[...].astype(wide),
         jnp.concatenate([mx_ref[...], mh_ref[...]], axis=-1)))
    h_ref[...] = h.astype(h_ref.dtype)
    xh_ref[...] = xh.astype(xh_ref.dtype)
    hh_ref[...] = hh.astype(hh_ref.dtype)
    mx_ref[...] = m[:, :half].astype(mx_ref.dtype)
    mh_ref[...] = m[:, half:].astype(mh_ref.dtype)


@functools.partial(jax.jit, static_argnames=("fmt", "block_b", "block_t",
                                             "interpret"))
def delta_gru_seq_int(xs, h0, x_hat0, h_hat0, m_x0, m_h0, w_x, w_h, th,
                      *, fmt=None, block_b: int | None = None,
                      block_t: int | None = None,
                      interpret: bool | None = None):
    """The int8-weight/int16-state variant of the fused sequence kernel.

    Same sequence-resident structure as ``delta_gru_seq`` (grid =
    (n_batch_tiles, T / block_t), state buffers VMEM-revisited, weights
    resident), but the datapath is ``core.fixed_point.gru_frame_step``:

      * ``fmt`` a ``GruFormats`` — integer-code operands: xs/h/x̂/ĥ are
        int16 codes, m_x/m_h int32 on the 24-bit saturating accumulator
        grid, weights int8, ``th`` a (1, 2) int32 [th_x, th_h].  Every
        Δ·W runs as exact byte-plane f32 dots against a one-time f32
        weight image in VMEM scratch (``fixed_point.
        packed_int8_dot_pair``), at any contraction length: bit-identical
        to the golden ``fixed_point.int_gru_scan`` scan.
      * ``fmt=None`` — identity-quant conformance mode: float operands
        (``th`` (1, 2) float32, both entries Δ_TH) through the SAME
        kernel skeleton, executing the float math in the float kernel's
        op order — bit-identical to ``delta_gru_seq`` and the XLA scan.
        This isolates the int kernel's plumbing (dispatch, block specs,
        state carry) from quantization in the differential fuzz suite.

    ``block_b``/``block_t`` tile the batch/time grid axes (numerics-
    invariant, autotunable); the tile and the kernel's VMEM limit follow
    ``int_vmem_plan``, which refuses weights that cannot stay resident.

    Returns ``(hs, (h, x̂, ĥ, m_x, m_h), nz_dx, nz_dh)``.
    """
    T, B, I = xs.shape
    H = h0.shape[1]
    assert h0.shape == h_hat0.shape == (B, H), (h0.shape, h_hat0.shape)
    assert x_hat0.shape == (B, I), (x_hat0.shape, (B, I))
    assert m_x0.shape == m_h0.shape == (B, 3 * H), (m_x0.shape, m_h0.shape)
    assert w_x.shape == (I, 3 * H), (w_x.shape, (I, 3 * H))
    assert w_h.shape == (H, 3 * H), (w_h.shape, (H, 3 * H))
    assert th.shape == (1, 2), th.shape
    bt = validate_divisor("delta_gru_seq_int", "block_t", block_t, "T", T)
    bb, vmem = int_vmem_plan(I, H, T, B, bt, block_b, interpret)
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
    kernel = functools.partial(_int_kernel, fmt=fmt, block_t=bt)
    state_spec = lambda d: pl.BlockSpec((bb, d), lambda b, t: (b, 0))
    fixed_spec = lambda s: pl.BlockSpec(s, lambda b, t: tuple(
        0 for _ in s))
    seq_spec = lambda d: pl.BlockSpec((bt, bb, d), lambda b, t: (t, b, 0))

    out_shapes = (
        jax.ShapeDtypeStruct((T, B, H), h0.dtype),      # hs
        jax.ShapeDtypeStruct((T, B), jnp.int32),        # nz_dx
        jax.ShapeDtypeStruct((T, B), jnp.int32),        # nz_dh
        jax.ShapeDtypeStruct((B, H), h0.dtype),         # h
        jax.ShapeDtypeStruct((B, I), x_hat0.dtype),     # x_hat
        jax.ShapeDtypeStruct((B, H), h_hat0.dtype),     # h_hat
        jax.ShapeDtypeStruct((B, 3 * H), m_x0.dtype),   # m_x
        jax.ShapeDtypeStruct((B, 3 * H), m_h0.dtype),   # m_h
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
        grid=(B // bb, T // bt),
        in_specs=[
            seq_spec(I),
            state_spec(H), state_spec(I), state_spec(H),
            state_spec(3 * H), state_spec(3 * H),
            fixed_spec((I, 3 * H)), fixed_spec((H, 3 * H)),
            fixed_spec((1, 2)),
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=([pltpu.VMEM((I, 3 * H), jnp.float32),
                         pltpu.VMEM((H, 3 * H), jnp.float32)]
                        if fmt is not None else []),
        compiler_params=compiler_params,
        name="delta_gru_seq_int",
        interpret=resolve_interpret(interpret),
    )(xs, h0, x_hat0, h_hat0, m_x0, m_h0, w_x, w_h, th)
    from repro.core.delta_gru import DeltaState
    return hs, DeltaState(h, x_hat, h_hat, m_x, m_h), nz_dx, nz_dh
