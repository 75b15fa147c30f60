"""The tight insertion upper bound per batch: the ``upper-bound`` layer
span under ``seed`` (operand copies, the +1 passes on the device, the
bound's fetch), mean over the window's batches (ms). None where no batch
of the window ran one, as in a program without the span."""

from bench import layer_spans


def read(run):
    calls = layer_spans.window_calls(run)
    if calls is None or not any(s.name == "upper-bound" for c in calls for s in c):
        return None
    return 1e3 * layer_spans.mean_per_call(run, "upper-bound", lambda s: s.seconds)
