"""Per-round stats and estimate fetch per call: the ``stats-reconstruct``
layer span (stat-buffer slices, any compile they cause, the estimate's
copy to the host), mean over the window's calls (s)."""

from bench import layer_spans


def read(run):
    return layer_spans.mean_per_call(run, "stats-reconstruct", lambda s: s.seconds)
