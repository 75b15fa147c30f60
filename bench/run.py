"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout; the cells are listed in BENCHMARK.json.
"""

import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
# the TPU runtime's own logs stay inside the checkout
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "bench" / ".trace" / "tpu_logs"))
os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
# the checkout root (for ``bench``) and the program's sources, never this
# directory itself, whose module names must not shadow others
sys.path[:1] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], root=ROOT))
