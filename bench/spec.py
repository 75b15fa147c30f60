"""What a cell is, read from ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by name:

* ``bench/configs/<config>.json`` — the deployment (graph, engine, guarantees);
* ``bench/mixes/<traffic>.json`` — the traffic parameters, read by the
  general loop the mix names (``loop``: ``decompose`` or ``serve``);
* ``bench/metrics/<metric>.py`` — a reader with ``read(run) -> float | None``;
  a metric split by the end-to-end metric it moves (``<name>.<suffix>``)
  falls back to ``bench/metrics/<name>.py`` where it has no file of its own.

So a later change adds a cell, a configuration, a mix or a metric by adding
files and entries, never by editing the harness.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list  # metric entries of BENCHMARK.json that this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_benchmark(root: pathlib.Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(root: pathlib.Path, name: str) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = json.loads((root / "bench" / "configs" / f"{w['config']}.json").read_text())
    mix = json.loads((root / "bench" / "mixes" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )


def load_reader(root: pathlib.Path, metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``, else of the
    file named by ``metric`` up to its last dot."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    if not path.exists() and "." in metric:
        path = path.with_name(metric.rsplit(".", 1)[0] + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
