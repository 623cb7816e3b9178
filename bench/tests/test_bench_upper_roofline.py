"""The stacked cell at a few slots, and ``gru_upper_roofline``'s work
count and trace reading on a hand-made ``.xplane.pb``."""
import pytest

from bench import costs, spec
from bench.tests.small import run_small, small_cell

UPPER = spec.load_module(spec.ROOT / "bench" / "metrics"
                         / "gru_upper_roofline.py", "gru_upper_roofline")
CELL = "edgedrnn-int8-utt-c4096"


def test_small_stacked_run_is_correct():
    """The 2-layer ΔGRU-768 cell through the program's int8 session on
    the CPU: every vote, decision and both layers' final state agree
    with the reference."""
    line = run_small(small_cell(CELL))
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert all(c["value"] == 0 for c in line["checks"].values())


def test_upper_work_is_the_second_layer_by_hand():
    cfg = small_cell(CELL).config
    H = 768
    w = UPPER.upper_work(cfg, slots=5, frames=3)
    # One layer of input H: 2·(H+H)·3H + 17H operations a frame and slot.
    assert w.ops == 5 * 3 * (2 * (H + H) * 3 * H + 17 * H)
    # Its input (int16 hidden codes) and output per frame, state in and
    # out (h, ĥ, x̂ int16; M int32), int8 weights.
    state = 2 * H * 2 + H * 2 + 6 * H * 4
    assert w.bytes == (5 * 3 * (H * 2 + H * 2) + 2 * 5 * state
                       + (H + H) * 3 * H)
    stack = costs.gru_call(cfg, 5, 3)
    assert w.ops < stack.ops


# ------------------------------------------------ a hand-made xplane
def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _plane(name, lines, metas=(), stat_metas=()):
    body = _f(1, 1) + _f(2, name)
    for ln in lines:
        body += _f(3, ln)
    for mid, meta in metas:
        body += _f(4, _f(1, mid) + _f(2, meta))
    for sid, sname in stat_metas:
        body += _f(5, _f(1, sid) + _f(2, _f(1, sid) + _f(2, sname)))
    return _f(1, body)


def _line(name, ts_ns, events):
    body = _f(1, 1) + _f(2, name) + _f(3, ts_ns)
    for mid, off_ps, dur_ps in events:
        body += _f(4, _f(1, mid) + _f(2, off_ps) + _f(3, dur_ps))
    return body


def _meta(mid, name, stats):
    body = _f(1, mid) + _f(2, name)
    for sid, kind, value in stats:
        body += _f(5, _f(1, sid) + _f(kind, value))
    return body


OP1 = "%delta_gru_seq_int.1 = (s16[32,8,768]) custom-call(s32[32,8,10])"
OP2 = "%delta_gru_seq_int.2 = (s16[32,8,768]) custom-call(s32[32,8,768])"
FEX = "%batched_iir_fex_int.3 = (s16[8,32,10]) custom-call(s16[8,4096])"
SCOPE = "jit(kws_audio_step)/kws.gru/kws.gru.{}/kws.gru/pallas_call"


def _xspace(scoped: bool = True) -> bytes:
    # Device 0, ops line at 1000 ns: layer 0 [0, 2000) ps, layer 1
    # [3000, 7000) ps twice (the second half outside the window), the FEx
    # op.  The layer-1 scope is an interned (ref) stat, layer 0's a
    # string.  Host: the window [1000, 1009) ns.
    tf_op, names = 7, 9
    metas = [(1, _meta(1, OP1, [(tf_op, 5, SCOPE.format(0))] if scoped
                       else [])),
             (2, _meta(2, OP2, [(tf_op, 7, names)] if scoped else [])),
             (3, _meta(3, FEX, [(tf_op, 5, "jit(kws_audio_step)/kws.fex")]))]
    stat_metas = [(tf_op, "tf_op"), (names, SCOPE.format(1))]
    dev = _plane("/device:TPU:0",
                 [_line("XLA Ops", 1000, [(1, 0, 2000), (2, 3000, 4000),
                                          (2, 7000, 4000), (3, 0, 500)])],
                 metas, stat_metas)
    host = _plane("/host:CPU", [_line("python3", 1000, [(1, 0, 9000)])],
                  [(1, _meta(1, "bench.window", []))])
    return dev + host


def test_scoped_ops_read_event_metadata():
    ops = UPPER.scoped_ops(_xspace())
    assert ops == {OP1: 0, OP2: 1}
    assert UPPER.scoped_ops(_xspace(scoped=False)) == {}


def test_upper_seconds_sums_upper_layers_inside_the_window(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    # Layer 1: 4 ns, then [1007, 1011) ns clipped to the window's 2 ns.
    assert UPPER.upper_seconds(path, "delta_gru_seq_int", 1) == \
        pytest.approx(6e-9)
    path.write_bytes(_xspace(scoped=False))
    assert UPPER.upper_seconds(path, "delta_gru_seq_int", 1) is None
