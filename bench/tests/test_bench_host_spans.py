"""The readers of the program's host spans and of the fused step's
device time, on hand-made run records and a hand-made trace."""
import dataclasses

import pytest

from bench import harness, spec
from bench.tests.small import small_cell

HOST = {"police_ms_per_step": "kws.police", "frame_ms_per_step": "kws.frame",
        "h2d_ms_per_step": "kws.h2d", "launch_ms_per_step": "kws.launch",
        "assemble_ms_per_step": "kws.assemble",
        "slots_ms_per_step": "kws.slots", "admit_ms_per_step": "kws.admit",
        "untraced_ms_per_step": "untraced"}


def _record(cell, report, steps=10):
    return harness.RunRecord(cell=cell, served={"steps": steps,
                                                "report": report},
                             trace=None, work=None, peaks={})


@pytest.mark.parametrize("metric,span", sorted(HOST.items()))
def test_host_span_readers(metric, span):
    cell = small_cell("int8-utt-c4096")
    reader = spec.reader(cell, metric)
    host = {name: 0.5 + i for i, name in enumerate(HOST.values())}
    assert reader.read(_record(cell, {"steps": 10,
                                      "host_ms_per_step": host})) \
        == host[span]
    # A program without the spans, or a run that stepped nothing.
    assert reader.read(_record(cell, {"steps": 10})) is None
    assert reader.read(_record(cell, {"steps": 0,
                                      "host_ms_per_step": host})) is None


def _xspace(tmp_path, cell_name):
    """A hand-made trace where the harness keeps the cell's trace: two
    TPU planes whose "XLA Modules" lines hold the fused step (3 + 5 µs on
    device 0, 4 + 6 µs on device 1) and another module, and an op line
    that the reader leaves alone."""
    from jax.profiler import ProfileData

    def plane(pid, name, modules):
        events = "".join(
            f"events {{ metadata_id: {m} offset_ps: {start * 10 ** 6} "
            f"duration_ps: {dur * 10 ** 6} }}\n"
            for m, start, dur in modules)
        return f"""planes {{ id: {pid} name: "{name}"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000
{events}  }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000
    events {{ metadata_id: 3 offset_ps: 0 duration_ps: 9000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1
    name: "jit_kws_audio_step(1234)" }} }}
  event_metadata {{ key: 2 value {{ id: 2
    name: "jit__reset_gru_slots(99)" }} }}
  event_metadata {{ key: 3 value {{ id: 3
    name: "%jit_kws_audio_step = f32[4] fusion()" }} }}
}}
"""
    text = (plane(1, "/device:TPU:0", [(1, 0, 3), (2, 4, 1), (1, 10, 5)])
            + plane(2, "/device:TPU:1", [(1, 0, 4), (1, 10, 6)])
            + 'planes { id: 3 name: "/host:CPU" }\n')
    run = tmp_path / ".bench_out" / "trace" / cell_name / "plugins" / \
        "profile" / "run"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))


@pytest.mark.parametrize("chips,want_ms", [(1, 8e-3 / 2),
                                           (2, (8e-3 + 10e-3) / 2 / 2)])
def test_step_device_ms_on_a_hand_made_trace(tmp_path, chips, want_ms):
    cell = dataclasses.replace(small_cell("int8-utt-c4096"), root=tmp_path,
                               chips=chips)
    reader = spec.reader(small_cell("int8-utt-c4096"), "step_device_ms")
    _xspace(tmp_path, cell.name)
    assert reader.module_seconds(next(
        tmp_path.rglob("*.xplane.pb"))) == pytest.approx({0: 8e-6,
                                                          1: 10e-6})
    assert reader.read(_record(cell, {}, steps=2)) == pytest.approx(want_ms)


def test_step_device_ms_is_absent_without_the_module(tmp_path):
    cell = dataclasses.replace(small_cell("int8-utt-c4096"), root=tmp_path)
    reader = spec.reader(small_cell("int8-utt-c4096"), "step_device_ms")
    assert reader.read(_record(cell, {}, steps=2)) is None   # no trace
    assert reader.ms_per_step({}, 2, 1) is None               # no module
    assert reader.ms_per_step({0: 1e-3, 1: 3e-3}, 4, 2) == \
        pytest.approx(0.5)
    assert reader.ms_per_step({0: 1e-3, 1: 3e-3}, 4, 1) == \
        pytest.approx(0.25)
