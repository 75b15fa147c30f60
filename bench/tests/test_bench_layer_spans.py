"""The readers of the program's layer spans on the tiny cells (CPU): each
reads a value from the window's calls, and none where the ring no longer
holds them or the program keeps none."""

import collections

import pytest

from bench import harness, layer_spans, spec
from bench.tests import tiny

READERS = {
    "decompose": ["stage_s.decompose", "loop_s.decompose", "h2d_mb.decompose"],
    "churn": ["stage_s.update", "loop_s.update", "stats_s.update", "h2d_mb.update",
              "stats_compiles.update"],
}
ROOTS = {"decompose": "kcore.decompose", "churn": "batch"}


def _window(tmp_path, monkeypatch, kind):
    """The tiny cell's root and its measured window, run untraced."""
    root = tiny.make_root(tmp_path)
    tiny.allow_cpu(monkeypatch)
    cell = spec.load_cell(root, f"tiny.{kind}")
    loop, _check = harness.LOOPS[cell.mix["loop"]]
    _setup, steps, win, _state = loop(root, cell, 2**31 + 11, 1.0, False, tmp_path / "trace")
    assert {m["name"] for m in cell.per_layer} >= set(READERS[kind])
    return root, harness.Run(cell.name, steps, win.seconds, win.compiles)


@pytest.mark.parametrize("kind", ["decompose", "churn"])
def test_the_layer_span_readers_read_the_window(tmp_path, monkeypatch, kind):
    from repro.obs import trace

    trace.reset()
    root, run = _window(tmp_path, monkeypatch, kind)
    calls = layer_spans.window_calls(run)
    assert len(calls) == len(run.steps)
    assert all(c[0].name == ROOTS[kind] for c in calls)
    values = {name: spec.load_reader(root, name)(run) for name in READERS[kind]}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert values[READERS[kind][2]] > 0                       # h2d_mb
    if kind == "decompose":
        phases = [s["phase_s"] for s in run.steps]
        assert values["loop_s.decompose"] == pytest.approx(
            sum(p["device-loop"] for p in phases) / len(phases))
        assert values["stage_s.decompose"] == pytest.approx(
            sum(p["stage"] for p in phases) / len(phases))
        assert values["loop_s.decompose"] <= max(p["device-converge"] for p in phases)
    else:
        converge = sum(s["converge_s"] for s in run.steps) / len(run.steps)
        assert 0 < values["loop_s.update"] + values["stats_s.update"] <= converge


@pytest.mark.parametrize("kind", ["decompose", "churn"])
def test_the_readers_give_none_once_the_ring_has_overflowed(tmp_path, monkeypatch, kind):
    from repro.obs import trace

    monkeypatch.setattr(trace.get_tracer(), "_layers", collections.deque(maxlen=3))
    root, run = _window(tmp_path, monkeypatch, kind)
    assert layer_spans.window_calls(run) is None
    for name in READERS[kind]:
        assert spec.load_reader(root, name)(run) is None


def test_the_readers_give_none_for_a_program_without_layer_spans(monkeypatch):
    from repro.obs import trace

    monkeypatch.delattr(trace, "recent_layers")
    run = harness.Run("tiny.churn", [{}] * 2, 1.0, 0)
    assert layer_spans.window_calls(run) is None
    assert layer_spans.mean_per_call(run, "stage", lambda s: s.seconds) is None
