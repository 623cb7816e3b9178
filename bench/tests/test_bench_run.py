"""Whole runs on the CPU at a few slots: the line, the check, the CLI."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench.tests.small import run_small, small_cell

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("name", ["int8-utt-c4096", "f32-utt-c4096",
                                  "int8-live-c128"])
def test_small_run_is_correct_and_line_has_contract_keys(name):
    cell = small_cell(name)
    line = run_small(cell)
    line.pop("setup_phases")
    assert list(line) == LINE_KEYS               # "checks" comes last
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(line)


def test_small_sharded_run_over_four_devices():
    """The four-chip cell's sharded slot pool, on four virtual CPUs."""
    code = (
        "import time, jax\n"
        "from bench.tests.small import small_cell\n"
        "from bench import harness\n"
        "cell = small_cell('int8-utt-c4096-x4')\n"
        "line = harness.run_cell(cell, 11, 0.5, False, time.perf_counter(),"
        " jax.devices())\n"
        "assert len(jax.devices()) == 4\n"
        "print(line['correct'], line['checks'])\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.startswith("True"), out.stdout


def _cli(cwd: pathlib.Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "int8-utt-c4096",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_without_a_tpu_exits_nonzero_and_prints_no_result():
    out = _cli(ROOT)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "not a TPU" in out.stderr


def test_cli_in_a_bare_benchmark_directory_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""
