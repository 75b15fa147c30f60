"""The harness end to end on the CPU, with the look for a chip skipped:
a cell defined only by new files, and the refusal off a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness, spec
from bench.tests import tiny

REPO = tiny.REPO


@pytest.mark.parametrize("kind", ["decompose", "churn"])
def test_a_cell_made_of_new_files_runs_and_is_correct(tmp_path, monkeypatch, kind):
    root = tiny.make_root(tmp_path)
    (root / "bench" / "metrics" / "batches_seen.py").write_text(
        "def read(run):\n    return float(len(run.steps))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "batches_seen", "unit": "count", "better": "higher",
                               "source": "host_clock", "layer": "load generator",
                               "moves": "update_s", "workloads": ["tiny.churn"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    tiny.allow_cpu(monkeypatch)
    cell = spec.load_cell(root, f"tiny.{kind}")
    line = harness.run_cell(root, cell, 2**31 + 7, 1.5, False)
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert all(c["value"] == 0 and c["limit"] == 0 for c in line["checks"].values())
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    if kind == "churn":
        assert [m["name"] for m in cell.per_layer][-1] == "batches_seen"
        assert spec.load_reader(root, "batches_seen")(harness.Run(cell.name, [{}] * 3, 1.0, 0)) == 3


def _run_cli(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu", **(env_extra or {})})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "graph500-s14.churn", "--seed",
         "4000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def _result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_off_a_tpu_the_harness_exits_nonzero_and_prints_no_result():
    proc = _run_cli(REPO)
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout)
    assert "no TPU" in proc.stderr


def test_without_the_program_the_harness_exits_nonzero(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".jax_cache", ".trace", "__pycache__"))
    proc = _run_cli(tmp_path)
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout)


def test_a_split_metric_falls_back_to_the_reader_of_its_base_name(tmp_path):
    root = tiny.make_root(tmp_path)
    run = harness.Run("tiny.decompose", [{}], 1.0, 4)
    assert spec.load_reader(root, "window_compiles.decompose")(run) == 4.0
    assert spec.load_reader(root, "window_compiles.update")(run) == 4.0
    (root / "bench" / "metrics" / "window_compiles.update.py").write_text(
        "def read(run):\n    return -1.0\n")
    assert spec.load_reader(root, "window_compiles.update")(run) == -1.0
    with pytest.raises(FileNotFoundError):
        spec.load_reader(root, "no_such_metric.update")
