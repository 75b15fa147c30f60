"""Host time patching the slack-padded CSR per batch
(``BatchResult.patch_s``), mean over the window's batches (ms)."""


def read(run):
    vals = [s["patch_s"] for s in run.steps if "patch_s" in s]
    return 1e3 * sum(vals) / len(vals) if vals else None
