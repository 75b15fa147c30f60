"""The engine's warm-start seed per batch: insertion upper bound and
initial frontier (``BatchResult.seed_s``), mean over the window's batches
(ms)."""


def read(run):
    vals = [s["seed_s"] for s in run.steps if "seed_s" in s]
    return 1e3 * sum(vals) / len(vals) if vals else None
