"""Host-to-device bytes per call: ``h2d_bytes`` summed over each call's
``stage`` layer spans (host arrays turned into device arrays; arrays
already on the device count 0), mean over the window's calls (MB, 10^6)."""

from bench import layer_spans


def read(run):
    mean = layer_spans.mean_per_call(run, "stage", lambda s: s.attrs.get("h2d_bytes", 0))
    return None if mean is None else mean / 1e6
