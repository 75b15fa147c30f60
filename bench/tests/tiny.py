"""A benchmark root at a size a test run holds: the real mixes and metric
readers, a 1,024-vertex Graph 500 configuration, and cells on it."""

from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent

TINY = {
    "name": "tiny",
    "source": "Graph 500 test graph",
    "generator": "graph500", "scale": 10, "edgefactor": 4,
    "A": 0.57, "B": 0.19, "C": 0.19, "D": 0.05, "graph_seed": 0,
    "engine": {"kcore_run_args": ["--fused"], "frontier": "auto"},
}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """Copy the benchmark's mixes and readers under ``tmp`` and list one
    cell per mix on the tiny configuration in its BENCHMARK.json."""
    root = tmp / "root"
    for sub in ("mixes", "metrics"):
        shutil.copytree(BENCH / sub, root / "bench" / sub)
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(TINY))
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = [f"tiny.{t}" for t in ("decompose", "churn")]
    kinds = {"decompose": [cells[0]], "update": [cells[1]]}
    moves = {"decompose_s": "decompose", "update_s": "update"}
    for m in real["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = kinds[moves[m["name"]]]
    for m in real["per_layer"]:
        m["workloads"] = kinds[moves[m["moves"]]]
    real["configs"] = [{"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                        "reduced": [], "why": "test"}]
    real["workloads"] = [{"name": c, "config": "tiny", "traffic": c.split(".")[1], "chips": 1,
                          "why": "test"} for c in cells]
    (root / "BENCHMARK.json").write_text(json.dumps(real))
    return root


def allow_cpu(monkeypatch) -> None:
    """Let a run proceed on the CPU: skip the look for a TPU, give the CPU
    a peaks row (for the readers that need one), and leave jax's
    compilation cache as the test process has it."""
    import jax

    from bench import device, harness

    monkeypatch.setattr(device, "require_tpu", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "configure_jax", lambda root: None)
    monkeypatch.setitem(device.PEAKS, jax.devices()[0].device_kind, {"hbm_bytes_per_s": 1e11})
