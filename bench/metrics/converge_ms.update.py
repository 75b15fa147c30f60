"""Re-convergence per batch (``BatchResult.converge_s``): staging of the
slot arrays and blocked layout on the host, any compile, and the device
loop, mean over the window's batches (ms)."""


def read(run):
    vals = [s["converge_s"] for s in run.steps if "converge_s" in s]
    return 1e3 * sum(vals) / len(vals) if vals else None
