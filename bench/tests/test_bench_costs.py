"""Operation, byte and peak accounting against hand-worked counts."""
import pytest

from bench import costs

# A small configuration: I = C = 2 channels, H = 3, K = 4, 8-sample frames.
F32 = {"numerics": "float32", "input_dim": 2, "hidden": 3, "classes": 4,
       "fex": {"frame_shift": 8},
       "dtypes": {"audio": "float32", "fex_state": "float32",
                  "features": "float32", "weights": "float32",
                  "hidden": "float32", "accumulator": "float32"}}
I8 = dict(F32, numerics="int8",
          dtypes={"audio": "int16", "fex_state": "int16", "features": "int16",
                  "weights": "int8", "hidden": "int16",
                  "accumulator": "int32"})


def test_fex_counts_by_hand():
    # 5 slots x 16 samples (2 frames) x 2 channels:
    #   16 ops a sample-channel: 5*16*2*16 = 2560; 10 a frame-channel:
    #   5*2*2*10 = 200.
    # bytes: audio 5*16*4 = 320, state 2*5*5*2*4 = 400, features
    #   5*2*2*4 = 80, coefficients 6*2*4 = 48.
    w = costs.fex_call(F32, slots=5, samples=16)
    assert w.ops == 2560 + 200
    assert w.bytes == 320 + 400 + 80 + 48
    w8 = costs.fex_call(I8, slots=5, samples=16)
    assert w8.ops == w.ops
    assert w8.bytes == 160 + 200 + 40 + 48


def test_gru_counts_by_hand():
    # per frame: 2*(2+3)*9 = 90 contraction, 6*3 = 18 accumulate,
    # 11*3 = 33 gates -> 141.  5 slots x 2 frames -> 1410.
    assert costs.gru_frame_ops(F32) == 141
    w = costs.gru_call(F32, slots=5, frames=2)
    assert w.ops == 1410
    # features in 5*2*2*4 = 80, hidden out 5*2*3*4 = 120; state per slot
    # (h, h_hat 6*4) + (x_hat 2*4) + (M 18*4) = 104, in and out for 5
    # slots = 1040; weights 5*9*4 = 180.
    assert w.bytes == 80 + 120 + 1040 + 180
    # int8: 40 + 60 + 2*5*(12 + 4 + 72) + 45
    assert costs.gru_call(I8, slots=5, frames=2).bytes == 40 + 60 + 880 + 45


def test_model_ops_per_frame_by_hand():
    # FEx 8*2*16 + 2*10 + ADC 8*7 = 332; GRU 141; FC 2*3*4 + 4 = 28.
    assert costs.model_ops_per_frame(F32) == 332 + 141 + 28


# The first layer as above; the second reads the first's hidden state.
F32_2 = dict(F32, layers=2)
I8_2 = dict(I8, layers=2)


def test_two_layer_counts_by_hand():
    # layer 1: 141 as above; layer 2 (I = H = 3): 2*(3+3)*9 = 108
    # contraction, 18 accumulate, 33 gates -> 159.  5 slots x 2 frames.
    assert costs.gru_frame_ops(F32_2) == 141 + 159
    w = costs.gru_call(F32_2, slots=5, frames=2)
    assert w.ops == 10 * 300
    # layer 2: hidden in 5*2*3*4 = 120, hidden out 120; state per slot
    # (h, h_hat 6*4) + (x_hat 3*4) + (M 18*4) = 108, in and out for 5
    # slots = 1080; weights 6*9*4 = 216.
    assert w.bytes == (80 + 120 + 1040 + 180) + (120 + 120 + 1080 + 216)
    # int8, layer 2 reads int16 codes: 60 + 60 + 2*5*(12 + 6 + 72) + 54.
    assert costs.gru_call(I8_2, slots=5, frames=2).bytes == \
        (40 + 60 + 880 + 45) + (60 + 60 + 900 + 54)
    # FEx 332 and FC 28 as for one layer.
    assert costs.model_ops_per_frame(F32_2) == 332 + 300 + 28


# The same widths as a ΔLSTM: four gates and a cell vector c.
F32_LSTM = dict(F32, cell="lstm",
                dtypes=dict(F32["dtypes"], cell="float32"))
I8_LSTM = dict(I8, cell="lstm", dtypes=dict(I8["dtypes"], cell="int16"))


def test_lstm_counts_by_hand():
    # per frame: 2*(2+3)*12 = 120 contraction, 2*4*3 = 24 accumulate,
    # 13*3 = 39 gates -> 183.  5 slots x 2 frames -> 1830.
    assert costs.gru_frame_ops(F32_LSTM) == 183
    w = costs.gru_call(F32_LSTM, slots=5, frames=2)
    assert w.ops == 1830
    # features in 80, hidden out 120; state per slot (h, h_hat 6*4) +
    # (x_hat 2*4) + (M 24*4) + (c 3*4) = 140, in and out for 5 slots =
    # 1400; weights 5*12*4 = 240.
    assert w.bytes == 80 + 120 + 1400 + 240
    # int8, c in int16: 40 + 60 + 2*5*(12 + 4 + 96 + 6) + 60.
    assert costs.gru_call(I8_LSTM, slots=5, frames=2).bytes == \
        40 + 60 + 1180 + 60
    # FEx 332 and FC 28 as for the ΔGRU.
    assert costs.model_ops_per_frame(F32_LSTM) == 332 + 183 + 28


def test_two_layer_lstm_counts_by_hand():
    f32, i8 = dict(F32_LSTM, layers=2), dict(I8_LSTM, layers=2)
    # layer 2 (I = H = 3): 2*(3+3)*12 = 144 contraction, 24 accumulate,
    # 39 gates -> 207.
    assert costs.gru_frame_ops(f32) == 183 + 207
    w = costs.gru_call(f32, slots=5, frames=2)
    assert w.ops == 10 * 390
    # layer 2: hidden in 120, out 120; state per slot 24 + 12 + 96 + 12
    # = 144, in and out for 5 slots = 1440; weights 6*12*4 = 288.
    assert w.bytes == (80 + 120 + 1400 + 240) + (120 + 120 + 1440 + 288)
    # int8: 60 + 60 + 2*5*(12 + 6 + 96 + 6) + 72.
    assert costs.gru_call(i8, slots=5, frames=2).bytes == \
        (40 + 60 + 1180 + 60) + (60 + 60 + 1200 + 72)
    assert costs.model_ops_per_frame(f32) == 332 + 390 + 28


# (fex ops, fex bytes, gru ops, gru bytes, model ops) of 1000 steps and
# 123457 served frames, recorded from the one-layer code before a
# configuration could state ``layers``; the stacked cell's from the code
# before a configuration could state ``cell``.
PINNED_WORK = {
    "int8-utt-c4096": (674365440000, 9249008000, 966787072000, 8574848000,
                       6484949296.0),
    "f32-utt-c4096": (674365440000, 18497776000, 966787072000,
                      14032384000, 6484949296.0),
    "int8-live-c128": (21073920000, 487664000, 30212096000, 3876736000,
                       6484949296.0),
    "int8-utt-c4096-x4": (2697461760000, 36995312000, 3867148288000,
                          34256768000, 6484949296.0),
    "edgedrnn-int8-utt-c4096": (674365440000, 9249008000, 350257938432000,
                                248248832000, 1324563239408.0),
}


@pytest.mark.parametrize("name", sorted(PINNED_WORK))
def test_committed_cells_work_is_pinned(name):
    from bench import spec
    cell = spec.find_cell(spec.load_benchmark(), name)
    t = cell.traffic
    work = costs.StepWork.of(cell, {"steps": 1000, "slots": t["slots"],
                                    "chunk_samples": t["chunk_samples"],
                                    "served_frames": 123457})
    assert (work.fex.ops, work.fex.bytes, work.gru.ops, work.gru.bytes,
            work.model_ops) == PINNED_WORK[name]
    assert work.steps == 1000


def test_peaks_and_roofline():
    p = costs.peaks_for("TPU v5 lite")
    assert p["bf16_ops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert costs.ops_peak(I8, p) == 393e12
    assert costs.ops_peak(F32, p) == 197e12
    w = costs.Work(ops=197e12, bytes=819e9 / 2)
    assert costs.least_time(w, 197e12, p) == pytest.approx(1.0)
    assert costs.least_time(w, None, p) == pytest.approx(0.5)


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5"])
def test_unknown_device_raises(kind):
    with pytest.raises(costs.UnknownDevice):
        costs.peaks_for(kind)
