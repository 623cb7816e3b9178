"""The trace reduction on a hand-made trace and on a recorded chip trace."""
import pathlib

import pytest

from bench import trace as T

FIXTURE = pathlib.Path(__file__).resolve().parent.parent / "fixtures" / \
    "trace_int8_utt_4steps.json"
DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def _ev(plane, line, name, start, dur):
    return T.Event(plane, line, name, float(start), float(dur))


def _hand_trace():
    # Window 0..100 ns.  Device 0: ops at [10,30) and [20,40) (overlap) and
    # [70,80); device 1: [0,50).  Host spans: dispatch [0,45), fetch
    # [45,100).  One op outside the window ([120,130)) and one event on
    # another line must be ignored.
    return [
        _ev(HOST, "python3", "bench.window", 0, 100),
        _ev(HOST, "python3", "bench.dispatch", 0, 45),
        _ev(HOST, "python3", "bench.fetch", 45, 55),
        _ev(DEV0, "XLA Ops", "%kern_a.1 = f32[4] custom-call(...)", 10, 20),
        _ev(DEV0, "XLA Ops", "%kern_a.2 = f32[4] custom-call(...)", 20, 20),
        _ev(DEV0, "XLA Ops", "%copy.7 = f32[4] copy(...)", 70, 10),
        _ev(DEV0, "XLA Ops", "%late = f32[4] copy(...)", 120, 10),
        _ev(DEV0, "XLA Modules", "jit_step(123)", 0, 100),
        _ev(DEV1, "XLA Ops", "%kern_a.3 = f32[4] custom-call(...)", 0, 50),
    ]


def test_hand_trace_one_device():
    s = T.reduce(_hand_trace(), n_devices=1)
    # busy = union [10,40) + [70,80) = 40 ns of a 100 ns window
    assert s.busy_s == pytest.approx(40e-9)
    assert s.window_s == pytest.approx(100e-9)
    assert s.devices == 1
    assert s.seconds_of("kern_a") == pytest.approx(40e-9)
    assert s.seconds_of("copy") == pytest.approx(10e-9)
    assert s.seconds_of("late") is None
    # gaps: [0,10) dispatch, [40,70) mostly fetch (25 of 30), [80,100) fetch
    assert s.idle_gaps == [["bench.fetch", pytest.approx(30e-9)],
                           ["bench.fetch", pytest.approx(20e-9)],
                           ["bench.dispatch", pytest.approx(10e-9)]]
    assert s.device_ops[0] == ["kern_a", pytest.approx(40e-9)]


def test_hand_trace_two_devices_average_busy():
    s = T.reduce(_hand_trace(), n_devices=2)
    assert s.devices == 2
    assert s.busy_s == pytest.approx((40e-9 + 50e-9) / 2)
    assert s.seconds_of("kern_a") == pytest.approx(90e-9)


def test_no_window_span_uses_device_extent():
    evs = [e for e in _hand_trace() if e.name != "bench.window"]
    s = T.reduce(evs, n_devices=1)
    assert s.window_s == pytest.approx(120e-9)      # 10 .. 130


def test_no_device_ops_is_an_error():
    with pytest.raises(ValueError):
        T.reduce([_ev(HOST, "python3", "bench.window", 0, 10)], 1)


@pytest.mark.parametrize("name,want", [
    ("%batched_iir_fex_int.1 = (s32[32,10,1024]) custom-call(...)",
     "batched_iir_fex_int"),
    ("%delta_gru_seq.12 = (f32[32]) custom-call(%x.1)", "delta_gru_seq"),
    ("%copy-done = f32[4] copy-done(%copy-start)", "copy-done"),
    ("fusion.3", "fusion"),
])
def test_op_names(name, want):
    assert T.op_name(name) == want


def test_recorded_chip_trace():
    """Four steps of the int8 utterance cell, recorded on a TPU v5e."""
    events = T.load(FIXTURE)
    s = T.reduce(events, n_devices=1)
    fex = [e for e in events if e.line == "XLA Ops"
           and e.name.startswith("%batched_iir_fex_int")]
    gru = [e for e in events if e.line == "XLA Ops"
           and e.name.startswith("%delta_gru_seq_int")]
    assert len(fex) == len(gru) == 4
    assert s.seconds_of("batched_iir_fex_int") == pytest.approx(
        sum(e.dur_ns for e in fex) * 1e-9)
    assert s.seconds_of("delta_gru_seq_int") == pytest.approx(
        sum(e.dur_ns for e in gru) * 1e-9)
    assert s.device_ops[0][0] == "batched_iir_fex_int"
    assert 0 < s.busy_s < s.window_s
    assert s.busy_s >= s.seconds_of("batched_iir_fex_int")
    assert all(name.startswith("bench.") for name, _ in s.idle_gaps)
    assert sum(g for _, g in s.idle_gaps) <= s.window_s - s.busy_s + 1e-12
