"""``correct`` comes out false for the control and for every fault a
one-chip cell can have, planted under the timed path (CPU, tiny size)."""

import pytest

from bench import faults, harness, spec
from bench.tests import tiny

CASES = [(kind, fault) for kind in ("decompose", "churn")
         for fault in ("round_cap", *faults.FAULTS)]


@pytest.mark.parametrize("kind,fault", CASES)
def test_a_planted_fault_makes_the_run_incorrect(tmp_path, monkeypatch, kind, fault):
    root = tiny.make_root(tmp_path)
    tiny.allow_cpu(monkeypatch)
    cell = spec.load_cell(root, f"tiny.{kind}")
    ctx = faults.round_cap(decompose_rounds=4) if fault == "round_cap" else faults.FAULTS[fault]()
    with ctx:
        line = harness.run_cell(root, cell, 2**32 + 3, 1.5, False)
    assert line["correct"] is False, line
    assert line["failed"] > 0
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
