"""Operations, bytes and peaks: the arithmetic behind roofline shares.

Counts are functions of the model's shapes (configuration) and the
traffic's (slots, chunk), never of the implementation's buffers, so they
read the same work whatever implements it.

  FEx, per sample and channel: two symmetric DF2T biquads (y = b0·x + s1,
    s1' = s2 − a1·y, s2' = −b0·x − a2·y: 3 multiplies, 3 adds each) and the
    envelope (|y|, two multiplies, an add): 16 operations; per frame and
    channel, the log2 compression onto the 12-bit grid: 10 operations.
    Bytes: audio in, filter state in and out, features out, coefficients.
  Recurrent stack, per frame and slot, dense as the kernel runs it
    today, summed over the configuration's layers (I = input_dim for the
    first, H for each layer above, which reads the hidden state below),
    with G the cell's gate count (``bench/model.py``'s ``CELLS``: ΔGRU 3,
    ΔLSTM 4): 2·(I+H)·G·H for the two contractions, 2·G·H to accumulate
    M, and the gates: ΔGRU 11H; ΔLSTM 13H (4H pre-activation sums, 3H
    sigmoid, H tanh(g), H f·c, H i·g, H add, H tanh(c), H o·tanh(c)).
    Bytes, each layer: its input in (features, or the hidden state
    below), hidden out, weights (I+H)·G·H, and the state in and out: h
    and ĥ, x̂, M as 2·G·H accumulator words, and for the ΔLSTM c, H words
    of ``dtypes["cell"]``.  The configuration's ``kernels.gru`` names
    the stack's recurrent kernel, whatever its cell.
  FC, per frame and slot: 2·H·K + K.
  ADC quantizer, per sample: 7 (clip, scale, round, clip).

Peaks are per chip, keyed by the device kind JAX reports.
"""
from __future__ import annotations

import dataclasses

from bench.model import CELLS, cell, layers

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s per chip.  No float32 or vector-unit rate is
# published: a float32 contraction is bounded by the bf16 rate (a lower
# bound on its time), and the FEx, which runs on the vector unit, by its
# bytes over HBM bandwidth alone.
PEAKS = {
    "TPU v5 lite": {"bf16_ops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}

BYTES = {"int8": 1, "int16": 2, "int32": 4, "float32": 4}

FEX_OPS_PER_SAMPLE_CHANNEL = 16
FEX_OPS_PER_FRAME_CHANNEL = 10
ADC_OPS_PER_SAMPLE = 7
FEX_STATE_ROWS = 5                     # 2 sections × 2 registers + envelope


class UnknownDevice(KeyError):
    """No published peaks for this device kind."""


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r}; "
                            f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def ops_peak(config: dict, peaks: dict) -> float:
    """The contraction peak that bounds this configuration's numerics."""
    return (peaks["int8_ops_per_s"] if config["numerics"] == "int8"
            else peaks["bf16_ops_per_s"])


@dataclasses.dataclass(frozen=True)
class Work:
    ops: float
    bytes: float

    def scaled(self, k: float) -> "Work":
        return Work(self.ops * k, self.bytes * k)


def fex_call(config: dict, slots: int, samples: int) -> Work:
    """One FEx call over ``slots`` × ``samples`` of audio."""
    C = int(config["input_dim"])
    shift = int(config["fex"]["frame_shift"])
    frames = samples // shift
    dt = config["dtypes"]
    ops = slots * (samples * C * FEX_OPS_PER_SAMPLE_CHANNEL
                   + frames * C * FEX_OPS_PER_FRAME_CHANNEL)
    nbytes = (slots * samples * BYTES[dt["audio"]]
              + 2 * slots * FEX_STATE_ROWS * C * BYTES[dt["fex_state"]]
              + slots * frames * C * BYTES[dt["features"]]
              + 6 * C * 4)
    return Work(ops, nbytes)


def _layer_inputs(config: dict) -> list[tuple[int, int]]:
    """(input width, bytes per input element) of each recurrent layer,
    the first first: the features, then the hidden state of the layer
    below."""
    I, H = int(config["input_dim"]), int(config["hidden"])
    dt = config["dtypes"]
    return ([(I, BYTES[dt["features"]])]
            + [(H, BYTES[dt["hidden"]])] * (layers(config) - 1))


GATE_OPS_PER_H = {"gru": 11, "lstm": 13}


def gru_frame_ops(config: dict) -> int:
    """Dense operations of the recurrent stack, one frame of one slot."""
    H = int(config["hidden"])
    G = CELLS[cell(config)].gates
    gate_ops = GATE_OPS_PER_H[cell(config)] * H
    return sum(2 * (I + H) * G * H + 2 * G * H + gate_ops
               for I, _ in _layer_inputs(config))


def gru_call(config: dict, slots: int, frames: int) -> Work:
    """The recurrent stack over ``slots`` × ``frames`` (dense): one call
    of the kernel per layer."""
    H = int(config["hidden"])
    spec = CELLS[cell(config)]
    dt = config["dtypes"]
    bh, ba = BYTES[dt["hidden"]], BYTES[dt["accumulator"]]
    bc = BYTES[dt["cell"]] if spec.carries_c else 0
    GH = spec.gates * H
    nbytes = 0
    for I, bi in _layer_inputs(config):
        state = (2 * H) * bh + I * bi + 2 * GH * ba + H * bc  # h, ĥ, x̂, M, c
        nbytes += (slots * frames * (I * bi + H * bh) + 2 * slots * state
                   + (I + H) * GH * BYTES[dt["weights"]])
    return Work(slots * frames * gru_frame_ops(config), nbytes)


def model_ops_per_frame(config: dict) -> int:
    """Useful operations of one served 16 ms decision, front to back."""
    C, H = int(config["input_dim"]), int(config["hidden"])
    K = int(config["classes"])
    shift = int(config["fex"]["frame_shift"])
    fex = (shift * C * FEX_OPS_PER_SAMPLE_CHANNEL
           + C * FEX_OPS_PER_FRAME_CHANNEL + shift * ADC_OPS_PER_SAMPLE)
    return fex + gru_frame_ops(config) + 2 * H * K + K


@dataclasses.dataclass(frozen=True)
class StepWork:
    """The window's work: each kernel over every step, and the model
    operations of the decisions served."""
    fex: Work
    gru: Work
    model_ops: float
    steps: int

    @classmethod
    def of(cls, cell, served: dict) -> "StepWork":
        cfg = cell.config
        steps, slots = int(served["steps"]), int(served["slots"])
        samples = int(served["chunk_samples"])
        frames = samples // int(cfg["fex"]["frame_shift"])
        return cls(fex=fex_call(cfg, slots, samples).scaled(steps),
                   gru=gru_call(cfg, slots, frames).scaled(steps),
                   model_ops=float(served["served_frames"])
                   * model_ops_per_frame(cfg),
                   steps=steps)


def least_time(work: Work, peak_ops: float | None, peaks: dict) -> float:
    """The roofline: the larger of operations over the operation peak
    (None: no published peak applies) and bytes over HBM bandwidth."""
    t_bytes = work.bytes / peaks["hbm_bytes_per_s"]
    if peak_ops is None:
        return t_bytes
    return max(work.ops / peak_ops, t_bytes)
