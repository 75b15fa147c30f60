"""The trickle cell: its entries load through ``spec.load_cell``, and its
three seed readers (``ub_ms``, ``ub_passes``, ``frontier_ms``) read a tiny
serve run of the trickle mix (CPU), and give None for a program whose
batches carry none of their spans."""

import json

import pytest

from bench import harness, spec
from bench.tests import tiny

CELL = "graph500-s16.trickle"
READERS = ("ub_ms", "ub_passes", "frontier_ms")


def test_the_trickle_cell_loads_with_its_readers():
    cell = spec.load_cell(tiny.REPO, CELL)
    assert cell.chips == 1
    assert cell.mix["loop"] == "serve"
    assert cell.mix["churn"] == {"frac": 0.0003, "stream_seed": 1, "warmup_batches": 3}
    assert cell.config["scale"] == 16 and cell.config["engine"]["frontier"] == "auto"
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "update_s"}
    layers = {m["name"]: m for m in cell.per_layer}
    assert set(READERS) <= set(layers)
    assert all(layers[r]["moves"] == "update_s" for r in READERS)
    assert {"seed_ms", "h2d_mb.update", "loop_s.update", "segsum_roofline"} <= set(layers)


def _trickle_root(tmp_path):
    """The tiny benchmark root with a ``tiny.trickle`` cell that reports
    every metric the tiny churn cell reports, and the three readers."""
    root = tiny.make_root(tmp_path)
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["workloads"].append({"name": "tiny.trickle", "config": "tiny", "traffic": "trickle",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.churn" in m.get("workloads", ()):
            m["workloads"].append("tiny.trickle")
    path.write_text(json.dumps(bench))
    return root


def test_the_seed_readers_read_a_tiny_trickle_run(tmp_path, monkeypatch):
    from repro.obs import trace

    trace.reset()
    root = _trickle_root(tmp_path)
    tiny.allow_cpu(monkeypatch)
    cell = spec.load_cell(root, "tiny.trickle")
    assert set(READERS) <= {m["name"] for m in cell.per_layer}
    loop, check = harness.LOOPS[cell.mix["loop"]]
    _setup, steps, win, state = loop(root, cell, 2**31 + 13, 1.0, False, tmp_path / "trace")
    run = harness.Run(cell.name, steps, win.seconds, win.compiles)
    assert steps and all(s["seed_strategy"] == "tight" for s in steps)
    values = {name: spec.load_reader(root, name)(run) for name in READERS}
    assert values["ub_passes"] >= 1, values
    assert values["ub_ms"] > 0 and values["frontier_ms"] > 0, values
    seed_ms = spec.load_reader(root, "seed_ms")(run)
    assert values["ub_ms"] + values["frontier_ms"] <= seed_ms
    checks, attempted, failed = check(cell, state, root)
    assert checks["core_mismatches"] == [0, 0] and attempted and not failed


@pytest.mark.parametrize("name", READERS)
def test_the_seed_readers_give_none_without_their_spans(name, monkeypatch):
    """A program whose batches carry no ``upper-bound`` or ``frontier``
    span (as before they were added): the readers give None, not 0."""
    from repro.obs import trace

    trace.reset()
    for _ in range(2):
        with trace.layer("batch"):
            with trace.layer("seed"):
                pass
    run = harness.Run("tiny.trickle", [{}] * 2, 1.0, 0)
    assert spec.load_reader(tiny.REPO, name)(run) is None
    trace.reset()
