"""The ``ub_sweeps`` reader: it loads for the trickle cell, reads a tiny
serve run of the trickle mix (CPU) at two sweeps a pass or more, and gives
None for a program whose batches carry no ``upper-bound`` span, or one
whose spans count passes but no sweeps."""

from bench import harness, spec
from bench.tests import tiny
from bench.tests.test_bench_trickle import _trickle_root

CELL = "graph500-s16.trickle"


def test_the_sweep_reader_loads_for_the_trickle_cell():
    cell = spec.load_cell(tiny.REPO, CELL)
    layers = {m["name"]: m for m in cell.per_layer}
    assert layers["ub_sweeps"]["moves"] == "update_s"
    assert layers["ub_sweeps"]["layer"] == layers["ub_passes"]["layer"]


def test_the_sweep_reader_reads_a_tiny_trickle_run(tmp_path, monkeypatch):
    from repro.obs import trace

    trace.reset()
    root = _trickle_root(tmp_path)
    tiny.allow_cpu(monkeypatch)
    cell = spec.load_cell(root, "tiny.trickle")
    assert "ub_sweeps" in {m["name"] for m in cell.per_layer}
    loop, _check = harness.LOOPS[cell.mix["loop"]]
    _setup, steps, win, _state = loop(root, cell, 2**31 + 13, 1.0, False, tmp_path / "trace")
    run = harness.Run(cell.name, steps, win.seconds, win.compiles)
    assert steps and all(s["seed_strategy"] == "tight" for s in steps)
    passes = spec.load_reader(root, "ub_passes")(run)
    sweeps = spec.load_reader(root, "ub_sweeps")(run)
    # each pass sweeps the propagation and the peel at least once
    assert passes >= 1 and sweeps >= 2 * passes, (passes, sweeps)
    trace.reset()


def _two_batches(**attrs):
    from repro.obs import trace

    trace.reset()
    for _ in range(2):
        with trace.layer("batch"):
            with trace.layer("seed"):
                if attrs:
                    with trace.layer("upper-bound", **attrs):
                        pass
    return harness.Run("tiny.trickle", [{}] * 2, 1.0, 0)


def test_the_sweep_reader_gives_none_without_the_upper_bound_span():
    from repro.obs import trace

    assert spec.load_reader(tiny.REPO, "ub_sweeps")(_two_batches()) is None
    trace.reset()


def test_the_sweep_reader_gives_none_without_sweep_counts():
    """A program whose ``upper-bound`` spans count passes but no sweeps
    (as before the sweeps were counted): ``ub_sweeps`` gives None while
    ``ub_passes`` reads the passes."""
    from repro.obs import trace

    run = _two_batches(passes=2)
    assert spec.load_reader(tiny.REPO, "ub_sweeps")(run) is None
    assert spec.load_reader(tiny.REPO, "ub_passes")(run) == 2
    trace.reset()


def test_the_sweep_reader_sums_both_counts_per_batch():
    from repro.obs import trace

    run = _two_batches(passes=2, prop_sweeps=5, peel_sweeps=17)
    assert spec.load_reader(tiny.REPO, "ub_sweeps")(run) == 22
    trace.reset()
