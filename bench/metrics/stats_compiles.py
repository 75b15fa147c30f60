"""XLA compiles (or cache loads) inside the window's ``stats-reconstruct``
layer spans, summed over the window's calls."""

from bench import layer_spans


def read(run):
    calls = layer_spans.window_calls(run)
    if calls is None:
        return None
    return float(sum(s.compiles for c in calls for s in c if s.name == "stats-reconstruct"))
