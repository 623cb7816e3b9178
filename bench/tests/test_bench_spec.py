"""BENCHMARK.json and the files its names resolve to."""
import dataclasses
import json
import pathlib
import re
import shutil

import pytest

from bench import harness, spec

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.load_benchmark(ROOT)


def test_top_level_keys_and_command():
    assert list(BENCH) == ["command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    # A full check of 24 cells must fit its 43200 s.
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_entries_have_exactly_the_contract_keys():
    for c in BENCH["configs"]:
        assert list(c) == ["name", "source", "file", "reduced", "why"]
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert c["reduced"] == []
    for w in BENCH["workloads"]:
        assert list(w) == ["name", "config", "traffic", "chips", "why"]
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    names = [x["name"] for k in ("configs", "workloads") for x in BENCH[k]]
    names += [m["name"] for k in ("end_to_end", "per_layer")
              for m in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for k in ("end_to_end", "per_layer")
               for m in BENCH[k])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_every_cell_reports_setup_another_e2e_and_what_its_layers_move():
    by_name = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in by_name and by_name["setup_s"]["bound"] <= 0.25
    for w in BENCH["workloads"]:
        cell = spec.find_cell(BENCH, w["name"], ROOT)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_resolves_every_file_by_name(name):
    cell = spec.find_cell(BENCH, name, ROOT)
    assert spec.driver(cell).serve
    assert spec.reference(cell).block
    for m in cell.per_layer:
        assert callable(spec.reader(cell, m["name"]).read)
    assert set(cell.limits["limits"])
    assert cell.config["name"] == cell.config_name


def test_per_layer_workloads_filter():
    utt = spec.find_cell(BENCH, "int8-utt-c4096", ROOT)
    live = spec.find_cell(BENCH, "int8-live-c128", ROOT)
    assert "fetch_ms_per_step" not in {m["name"] for m in utt.per_layer}
    assert "fetch_ms_per_step" in {m["name"] for m in live.per_layer}
    assert "decision_p99_ms" not in {m["name"] for m in utt.end_to_end}


def test_unknown_names_raise():
    with pytest.raises(spec.SpecError):
        spec.find_cell(BENCH, "no-such-cell", ROOT)


def test_a_cell_added_as_files_alone_is_found(tmp_path):
    """A later change adds a cell by adding files and entries only."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    traffic = json.loads((ROOT / "bench/traffic/live-c128.json").read_text())
    traffic["slots"] = 512
    (tmp_path / "bench/traffic/test-only-mix.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench/limits/test-only-cell.json").write_text(
        (ROOT / "bench/limits/int8-live-c128.json").read_text())
    (tmp_path / "bench/metrics/test_only_metric.py").write_text(
        "def read(rec):\n    return 42.0\n")
    b["workloads"].append({"name": "test-only-cell", "config": "deltakws-f32",
                           "traffic": "test-only-mix", "chips": 1,
                           "why": "test"})
    b["per_layer"].append({"name": "test_only_metric", "unit": "%",
                           "better": "higher", "source": "device_trace",
                           "layer": "device", "moves": "rt_streams",
                           "workloads": ["test-only-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.find_cell(spec.load_benchmark(tmp_path), "test-only-cell",
                          tmp_path)
    assert cell.traffic["slots"] == 512
    assert cell.config["numerics"] == "float32"
    assert spec.driver(cell).__name__ != spec.driver(
        spec.find_cell(BENCH, "int8-live-c128", ROOT)).__name__
    assert spec.reader(cell, "test_only_metric").read(None) == 42.0
    assert "test_only_metric" in {m["name"] for m in cell.per_layer}
    for p, data in before.items():
        assert p.read_bytes() == data


def test_program_config_check_refuses_a_different_width():
    from repro.configs import get_config
    from repro.frontend import FExConfig
    cfg = json.loads((ROOT / "bench/configs/deltakws-int8.json").read_text())
    harness._check_program_config(cfg, get_config("deltakws"), FExConfig())
    cfg["hidden"] = 128
    with pytest.raises(harness.RunError):
        harness._check_program_config(cfg, get_config("deltakws"),
                                      FExConfig())


@pytest.mark.parametrize("name", ["int8-utt-c4096", "int8-live-c128"])
def test_a_run_of_a_two_layer_config_refuses_the_one_layer_program(name):
    import dataclasses
    from bench.tests.small import run_small, small_cell
    cell = small_cell(name)
    two = dataclasses.replace(cell, config=dict(cell.config, layers=2))
    with pytest.raises(harness.RunError, match="layers"):
        run_small(two)


def test_a_two_layer_config_added_as_a_file_alone(tmp_path):
    """A stacked configuration is a config file with ``layers``: weights,
    counts and reference follow it, and no file under bench/ changes."""
    from bench import costs, model
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((ROOT / "bench/configs/deltakws-f32.json").read_text())
    cfg.update(name="test-two-layer", layers=2)
    (tmp_path / "bench/configs/test-two-layer.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/limits/test-two-layer-utt.json").write_text(
        (ROOT / "bench/limits/f32-utt-c4096.json").read_text())
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "test-two-layer", "source": "test",
                         "file": "bench/configs/test-two-layer.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "test-two-layer-utt",
                           "config": "test-two-layer",
                           "traffic": "utt-c4096", "chips": 1,
                           "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.find_cell(spec.load_benchmark(tmp_path),
                          "test-two-layer-utt", tmp_path)
    one = spec.find_cell(BENCH, "f32-utt-c4096", ROOT)
    params = model.make_params(cell.config, 3)
    assert {"w_x_1", "w_h_1", "b_1"} <= set(params)
    served = {"steps": 10, "slots": 8, "chunk_samples": 4096,
              "served_frames": 100}
    two_work = costs.StepWork.of(cell, served)
    one_work = costs.StepWork.of(one, served)
    assert two_work.fex == one_work.fex
    assert two_work.gru.ops > one_work.gru.ops
    assert two_work.model_ops > one_work.model_ops
    ref_model, _ = spec.reference(cell).build(cell.config, params)
    assert len(ref_model.gru) == 2
    from repro.configs import get_config
    from repro.frontend import FExConfig
    with pytest.raises(harness.RunError, match="layers"):
        harness._check_program_config(cell.config, get_config("deltakws"),
                                      FExConfig())
    for p, data in before.items():
        assert p.read_bytes() == data


def test_an_lstm_config_added_as_a_file_alone(tmp_path):
    """A ΔLSTM configuration is a config file with ``cell``: weights,
    counts and reference follow it, today's program (a ΔGRU) is refused
    by name, and no file under bench/ changes."""
    from bench import costs, model
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((ROOT / "bench/configs/deltakws-int8.json").read_text())
    cfg.update(name="test-lstm", cell="lstm", layers=2,
               formats=dict(cfg["formats"], cell_frac=12),
               dtypes=dict(cfg["dtypes"], cell="int16"))
    (tmp_path / "bench/configs/test-lstm.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/limits/test-lstm-utt.json").write_text(
        (ROOT / "bench/limits/int8-utt-c4096.json").read_text())
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "test-lstm", "source": "test",
                         "file": "bench/configs/test-lstm.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "test-lstm-utt", "config": "test-lstm",
                           "traffic": "utt-c4096", "chips": 1,
                           "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.find_cell(spec.load_benchmark(tmp_path), "test-lstm-utt",
                          tmp_path)
    assert model.cell(cell.config) == "lstm"
    params = model.make_params(cell.config, 2 ** 37 + 1)
    H, I = cfg["hidden"], cfg["input_dim"]
    assert params["w_x"].shape == (I, 4 * H)
    assert params["w_h"].shape == params["w_x_1"].shape == (H, 4 * H)
    assert params["b"].shape == params["b_1"].shape == (4 * H,)
    gru = dataclasses.replace(cell, config=dict(cell.config, cell="gru"))
    served = {"steps": 10, "slots": 8, "chunk_samples": 4096,
              "served_frames": 100}
    lstm_work = costs.StepWork.of(cell, served)
    gru_work = costs.StepWork.of(gru, served)
    assert lstm_work.fex == gru_work.fex
    assert lstm_work.gru.ops > gru_work.gru.ops
    assert lstm_work.gru.bytes > gru_work.gru.bytes
    assert lstm_work.model_ops > gru_work.model_ops
    ref = spec.reference(cell)
    ref_model, static = ref.build(cell.config, params)
    assert len(ref_model.gru) == 2 and static.cell == "lstm"
    assert static.cell_frac == 12
    state = ref.init_state(ref_model, static, 3)
    assert [c.shape for c in state.c] == [(3, H), (3, H)]
    assert state.m_x[1].shape == (3, 4 * H)
    from repro.configs import get_config
    from repro.frontend import FExConfig
    arch = dataclasses.replace(get_config("deltakws"), num_layers=2)
    with pytest.raises(harness.RunError, match="'cell'"):
        harness._check_program_config(cell.config, arch, FExConfig())
    for p, data in before.items():
        assert p.read_bytes() == data


@pytest.mark.parametrize("name, want", [(None, "gru"), ("gru", "gru"),
                                        ("lstm", "lstm"), ("rnn", None)])
def test_the_cell_a_config_states(name, want):
    from bench import model
    cfg = {} if name is None else {"cell": name}
    if want is None:
        with pytest.raises(ValueError, match="rnn"):
            model.cell(cfg)
    else:
        assert model.cell(cfg) == want
