"""The initial frontier per batch: the ``frontier`` layer span under
``seed`` (touched vertices, seed changes and their receivers over the live
arcs, on the host), mean over the window's batches (ms). None where no
batch of the window has the span, as in a program without it."""

from bench import layer_spans


def read(run):
    calls = layer_spans.window_calls(run)
    if calls is None or not any(s.name == "frontier" for c in calls for s in c):
        return None
    return 1e3 * layer_spans.mean_per_call(run, "frontier", lambda s: s.seconds)
