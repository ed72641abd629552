"""The harness finds everything by name, and refuses to measure without a
chip."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from benchmarks.chip import harness, run

ROOT = harness.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_loads_with_its_files(name):
    cell = harness.load_cell(name)
    assert cell.sizes.params > 0
    assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
    assert cell.per_layer
    assert cell.limits and all("limit" in v for v in cell.limits.values())
    assert harness.drive_module(cell).run


def test_every_per_layer_metric_has_a_reader():
    for m in BENCH["per_layer"]:
        path = harness.HERE / "metrics" / f"{m['name']}.py"
        assert path.is_file(), path
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


def test_readers_return_nothing_without_a_trace():
    out = harness.Outcome(e2e={}, counters={}, window_s=1.0, attempted=0,
                          failed=0, numbers={}, readings={},
                          memory_peak_bytes=0, compiles_in_window=0)
    for m in BENCH["per_layer"]:
        assert harness.read_metric(m["name"], out) is None, m["name"]


def test_judge_fails_missing_and_nan_numbers():
    cell = harness.load_cell(CELLS[0])
    (key,) = cell.limits
    assert harness.judge(cell, {})[key]["ok"] is False
    assert harness.judge(cell, {key: float("nan")})[key]["ok"] is False
    assert harness.judge(cell, {key: 0.0})[key]["ok"] is True


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """Without the program (only BENCHMARK.json and benchmarks/chip) a run
    fails and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_unknown_cell():
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell")


def test_unit_of():
    assert run.unit_of("setup_s") == "s"
