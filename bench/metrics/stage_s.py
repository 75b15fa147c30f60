"""Host staging per call: the summed ``stage`` layer spans of each call
(ELL and blocked layouts, slot padding, host-to-device copies), mean over
the window's calls (s)."""

from bench import layer_spans


def read(run):
    return layer_spans.mean_per_call(run, "stage", lambda s: s.seconds)
