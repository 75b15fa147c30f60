"""The control of ``correct``: run cells with a fixed superstep budget.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 20

Decompositions stop two supersteps before the configuration's graph
converges (``sizes.rounds`` of its file) and churn batches after two
(``bench.faults.round_cap``); everything else is the cell's own run. Each
run prints its result line; every one should read ``correct: false``.
The benchmark's own runs never do this.
"""

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:1] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "bench" / ".trace" / "tpu_logs"))
os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

from bench import faults, harness, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    cap = int(cell.config.get("sizes", {}).get("rounds", 4)) - 2
    for seed in (int(s) for s in args.seeds.split(",")):
        with faults.round_cap(decompose_rounds=cap):
            line = harness.run_cell(ROOT, cell, seed, args.seconds, False)
        print(json.dumps({"control": "round_cap", "workload": cell.name, "seed": seed, **line}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
