"""+1 passes of the tight insertion upper bound per batch: the ``passes``
attribute of the ``upper-bound`` layer span (every pass run, the last,
which raises nothing, included), mean over the window's batches. None
where no batch of the window counted them, as in a program without the
span."""

from bench import layer_spans


def read(run):
    calls = layer_spans.window_calls(run)
    if calls is None or not any(s.name == "upper-bound" and "passes" in s.attrs
                                for c in calls for s in c):
        return None
    return layer_spans.mean_per_call(run, "upper-bound", lambda s: s.attrs.get("passes", 0))
