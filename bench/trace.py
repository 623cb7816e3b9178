"""Reduce a profiler trace to device busy time, op times and idle gaps.

A trace is read into plain events ``(plane, line, name, start_ns,
dur_ns)`` (``load``), and ``reduce`` turns those into a ``Summary``:

  busy_s      per device, the union of the intervals in which an op ran
              on it, inside the measured window; averaged over devices;
  window_s    the window: the host span ``bench.window`` when the trace
              holds it, else first to last device op;
  op_s        total device seconds per op name (the ``.N`` suffix XLA
              gives repeated instructions stripped), over all devices;
  device_ops  the ten op names that took most device time;
  idle_gaps   the ten longest gaps between device ops on any device in
              the window, each named by the benchmark's host span that
              covers most of it (what the host was doing meanwhile).

Device ops are the events on a device plane's op line ("XLA Ops").
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import re

from bench.probes import SPAN_WINDOW, SPANS

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINES = ("XLA Ops",)
UNSPANNED = "host outside bench spans"
# A TPU op event is named by its HLO instruction: "%name.N = type op(...)".
_HLO_NAME = re.compile(r"^%?([\w.-]+?)(?:\.\d+)? = ")
_SUFFIX = re.compile(r"\.\d+$")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Summary:
    busy_s: float
    window_s: float
    devices: int
    op_s: dict
    device_ops: list
    idle_gaps: list

    def seconds_of(self, name: str) -> float | None:
        """Device seconds of the ops named ``name`` (None: none ran)."""
        return self.op_s.get(name)


def load(path: pathlib.Path) -> list[Event]:
    """Events of an ``.xplane.pb`` file, or of a JSON list of events."""
    if path.suffix == ".json":
        return [Event(*e) for e in json.loads(path.read_text())]
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def find_trace(logdir: pathlib.Path) -> pathlib.Path:
    files = sorted(logdir.glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def op_name(name: str) -> str:
    """The instruction name of a device op event, without XLA's ``.N``."""
    m = _HLO_NAME.match(name)
    return m.group(1) if m else _SUFFIX.sub("", name)


def reduce(events: list[Event], n_devices: int) -> Summary:
    dev_ops: dict[str, list[Event]] = {}
    for ev in events:
        if DEVICE_PLANE.match(ev.plane) and ev.line in OP_LINES:
            dev_ops.setdefault(ev.plane, []).append(ev)
    if not dev_ops:
        raise ValueError("the trace holds no device ops")
    planes = sorted(dev_ops, key=lambda p: int(DEVICE_PLANE.match(p)[1]))
    planes = planes[:n_devices]

    spans = [ev for ev in events if ev.name in SPANS]
    windows = [ev for ev in events if ev.name == SPAN_WINDOW]
    if windows:
        w0, w1 = windows[0].start_ns, windows[0].end_ns
    else:
        w0 = min(e.start_ns for p in planes for e in dev_ops[p])
        w1 = max(e.end_ns for p in planes for e in dev_ops[p])

    busy, op_s, gaps = [], {}, []
    for p in planes:
        ivs = []
        for e in dev_ops[p]:
            s, t = max(e.start_ns, w0), min(e.end_ns, w1)
            if t <= s:
                continue
            ivs.append((s, t))
            key = op_name(e.name)
            op_s[key] = op_s.get(key, 0.0) + (t - s) * 1e-9
        merged = _union(ivs)
        busy.append(sum(t - s for s, t in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, t in zip(edges[0::2], edges[1::2]):
            if t > s:
                gaps.append((s, t))
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_cover(spans, s, t), (t - s) * 1e-9] for s, t in gaps[:10]]
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    return Summary(busy_s=sum(busy) / len(busy), window_s=(w1 - w0) * 1e-9,
                   devices=len(planes), op_s=op_s,
                   device_ops=[[k, v] for k, v in top], idle_gaps=idle)


def _cover(spans: list[Event], s: float, t: float) -> str:
    """The host span that overlaps [s, t) most."""
    best, name = 0.0, UNSPANNED
    for ev in spans:
        o = min(t, ev.end_ns) - max(s, ev.start_ns)
        if o > best:
            best, name = o, ev.name
    return name


def reduce_dir(logdir: pathlib.Path, n_devices: int) -> Summary:
    return reduce(load(find_trace(logdir)), n_devices)
