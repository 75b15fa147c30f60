"""The device while_loop per call: the ``device-loop`` layer span, from the
call of the compiled program until ``block_until_ready`` returns, mean over
the window's calls (s)."""

from bench import layer_spans


def read(run):
    return layer_spans.mean_per_call(run, "device-loop", lambda s: s.seconds)
