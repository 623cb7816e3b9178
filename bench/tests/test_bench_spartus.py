"""The ``spartus-2l1024-int8`` configuration and its cell.

The configuration file loads through ``BENCHMARK.json``, its work counts
match a count by hand (25.29 M dense operations a frame and slot), the
program's ``spartus`` configuration passes the harness's check of sizes,
cell included, and its ΔLSTM metric reads the gate roofline's layers
above the first.
"""
import pathlib

import pytest

from bench import costs, harness, model, spec
from bench.tests.small import run_small, small_cell

CELL = "spartus-int8-utt-c4096"


def _cell():
    return spec.find_cell(spec.load_benchmark(), CELL)


def test_the_config_loads_with_its_published_sizes():
    cfg = _cell().config
    assert cfg["name"] == "spartus-2l1024-int8"
    assert (model.cell(cfg), model.layers(cfg), cfg["hidden"]) == \
        ("lstm", 2, 1024)
    assert cfg["reduced"] == [] and cfg["assumed"]
    assert cfg["formats"]["cell_frac"] == 12
    assert cfg["dtypes"]["cell"] == "int16"
    assert cfg["kernels"] == {"fex": "batched_iir_fex_int",
                              "gru": "delta_lstm_seq_int"}
    names = [m["name"] for m in _cell().per_layer]
    assert "lstm_upper_roofline" in names
    assert "gru_upper_roofline" not in names


def test_step_work_matches_the_hand_count():
    # layer 1: 2*(10+1024)*4096 contraction + 2*4096 accumulate + 13*1024
    #   gates = 8,470,528 + 8,192 + 13,312 = 8,492,032;
    # layer 2: 2*2048*4096 + 8,192 + 13,312 = 16,798,720.
    cfg = _cell().config
    assert costs.gru_frame_ops(cfg) == 8_492_032 + 16_798_720 == 25_290_752
    served = {"steps": 3, "slots": 1024, "chunk_samples": 4096,
              "served_frames": 1000}
    work = costs.StepWork.of(_cell(), served)
    assert work.gru.ops == 3 * 1024 * 32 * 25_290_752
    # weights (10+1024)*4096 + 2048*4096 int8 bytes each step
    weights = (1034 + 2048) * 4096
    state = lambda I: 2 * 1024 * (2 * 1024 * 2 + I * 2 + 2 * 4096 * 4
                                  + 1024 * 2)
    seq = 1024 * 32 * ((10 + 1024) * 2 + (1024 + 1024) * 2)
    assert work.gru.bytes == 3 * (weights + state(10) + state(1024) + seq)


def test_the_harness_accepts_the_programs_spartus_config():
    from repro.configs import get_config
    from repro.frontend import FeatureExtractor
    arch = get_config("spartus")
    assert arch.cell == "lstm"
    cfg = _cell().config
    harness._check_program_config(cfg, arch, FeatureExtractor().cfg)
    gru = dict(cfg, cell="gru")
    with pytest.raises(harness.RunError, match="cell"):
        harness._check_program_config(gru, arch, FeatureExtractor().cfg)


def test_lstm_upper_roofline_reads_the_upper_layers_work():
    cell = _cell()
    reader = spec.reader(cell, "lstm_upper_roofline")
    upper = spec.load_module(pathlib.Path(reader.__file__).with_name(
        "gru_upper_roofline.py"), "metric gru_upper_roofline")
    assert reader.UPPER is upper
    w = upper.upper_work(cell.config, 1024, 32)
    assert w.ops == 1024 * 32 * 16_798_720


def test_the_cell_runs_correct_at_a_small_size():
    """The whole run on the CPU at 8 slots: the program's ΔLSTM-1024
    stack against the reference, every number within its limit."""
    line = run_small(small_cell(CELL, slots=8, pool=8))
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
