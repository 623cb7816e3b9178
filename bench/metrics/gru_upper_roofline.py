"""The ΔGRU layers above the first: their share of the roofline, in %.

The least time the chip could take for the dense work of every ΔGRU
layer above the first over the window's steps — the stack's
``costs.gru_call`` less the same configuration at one layer, at the
peak of the configuration's numerics and HBM bandwidth — divided by the
summed device time, inside the window, of the ops named as the
configuration's ΔGRU kernel whose metadata carries the scope
``kws.gru.<l>`` with l ≥ 1 (the program runs layer l under that named
scope, l = 0 first).  It reads the share of the layers whose input is a
hidden state as wide as their own.  Absent when the trace holds no such
op: a one-layer model, or a program that runs no such scope.

On a TPU op line an event's own stats hold only its timing; the scope
is in its event metadata: on TPU v5e the ``tf_op`` stat carries the
op's name-scope path, e.g. ``jit(kws_audio_step)/kws.gru/kws.gru.1/
kws.gru/jit(delta_gru_seq_int)/delta_gru_seq_int/pallas_call``; any
string stat is searched.  ``jax.profiler.ProfileData`` does not expose
event metadata, so
``scoped_ops`` reads the ``.xplane.pb`` wire format for it (XSpace →
device XPlane → event_metadata / stat_metadata), skipping the event
lines; the times come from ``ProfileData`` as for ``gru_roofline``.
Moves ``rt_streams``.
"""
from __future__ import annotations

import re

from bench import costs, trace
from bench.probes import SPAN_WINDOW

SCOPE = re.compile(r"(?:^|/)kws\.gru\.(\d+)(?:/|$)")


def upper_work(config: dict, slots: int, frames: int) -> costs.Work:
    """Dense work of one call of every layer above the first."""
    stack = costs.gru_call(config, slots, frames)
    first = costs.gru_call(dict(config, layers=1), slots, frames)
    return costs.Work(stack.ops - first.ops, stack.bytes - first.bytes)


# ------------------------------------------------ xplane wire format
# Field numbers of tsl/profiler/protobuf/xplane.proto.
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
MAP_KEY, MAP_VALUE = 1, 2
META_NAME, META_STATS = 2, 5
STAT_STR, STAT_REF = 5, 7
STAT_META_ID, STAT_META_NAME = 1, 2


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, value) of one serialized message: an int for a
    varint, a memoryview for a length-delimited field."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _map_entries(raw_entries):
    for entry in raw_entries:
        kv = dict(fields(entry))
        yield kv.get(MAP_KEY, 0), kv.get(MAP_VALUE, b"")


def scoped_ops(data: bytes) -> dict[str, int]:
    """Device op name → the ``kws.gru.<l>`` layer its metadata names."""
    out: dict[str, int] = {}
    for field, plane in fields(data):
        if field != SPACE_PLANES:
            continue
        name, metas, stat_metas = "", [], []
        for f, v in fields(plane):
            if f == PLANE_NAME:
                name = bytes(v).decode("utf-8", "replace")
            elif f == PLANE_EVENT_METADATA:
                metas.append(v)
            elif f == PLANE_STAT_METADATA:
                stat_metas.append(v)
        if not trace.DEVICE_PLANE.match(name):
            continue
        strings = {}
        for key, value in _map_entries(stat_metas):
            strings[key] = next((bytes(v).decode("utf-8", "replace")
                                 for f, v in fields(value)
                                 if f == STAT_META_NAME), "")
        for _, value in _map_entries(metas):
            op, texts = "", []
            for f, v in fields(value):
                if f == META_NAME:
                    op = bytes(v).decode("utf-8", "replace")
                elif f == META_STATS:
                    for sf, sv in fields(v):
                        if sf == STAT_STR:
                            texts.append(bytes(sv).decode("utf-8",
                                                          "replace"))
                        elif sf == STAT_REF:
                            texts.append(strings.get(sv, ""))
            for text in texts:
                m = SCOPE.search(text)
                if m:
                    out[op] = int(m[1])
                    break
    return out


def upper_seconds(path, kernel: str, chips: int) -> float | None:
    """Device seconds inside the window of ``kernel`` ops of layers
    l ≥ 1 on the first ``chips`` devices (None: no such op)."""
    from jax.profiler import ProfileData
    layer = scoped_ops(path.read_bytes())
    upper = {op for op, l in layer.items()
             if l >= 1 and trace.op_name(op) == kernel}
    if not upper:
        return None
    data = ProfileData.from_file(str(path))
    planes = list(data.planes)
    w0 = w1 = None
    for plane in planes:
        if trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == SPAN_WINDOW:
                    w0, w1 = ev.start_ns, ev.start_ns + ev.duration_ns
    total = 0.0
    for plane in planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if not m or int(m[1]) >= chips:
            continue
        for line in plane.lines:
            if line.name not in trace.OP_LINES:
                continue
            for ev in line.events:
                if ev.name not in upper:
                    continue
                s, t = ev.start_ns, ev.start_ns + ev.duration_ns
                if w0 is not None:
                    s, t = max(s, w0), min(t, w1)
                total += max(t - s, 0.0) * 1e-9
    return total


def read(rec):
    logdir = rec.cell.root / ".bench_out" / "trace" / rec.cell.name
    try:
        path = trace.find_trace(logdir)
    except FileNotFoundError:
        return None
    t = upper_seconds(path, rec.cell.config["kernels"]["gru"],
                      rec.cell.chips)
    if not t:
        return None
    cfg = rec.cell.config
    frames = (int(rec.served["chunk_samples"])
              // int(cfg["fex"]["frame_shift"]))
    work = upper_work(cfg, int(rec.served["slots"]), frames).scaled(
        int(rec.served["steps"]))
    peak = costs.ops_peak(cfg, rec.peaks)
    return 100.0 * costs.least_time(work, peak, rec.peaks) / t
