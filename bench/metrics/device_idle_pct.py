"""Share of the traced window in which no op ran on the device (%),
averaged over the chips: 100 * (1 - busy_s / window_s)."""


def read(run):
    r = run.reduced
    if r is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
