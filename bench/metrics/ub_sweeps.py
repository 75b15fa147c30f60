"""Sweeps of the tight insertion upper bound per batch: the
``prop_sweeps`` + ``peel_sweeps`` attributes of the ``upper-bound`` layer
span (the max-min propagation's and the support peel's sweeps over every
arc slot, summed over the bound's +1 passes), mean over the window's
batches. ``ub_ms`` over it is the cost of a sweep. None where no batch of
the window counted them, as in a program whose span carries no sweep
counts."""

from bench import layer_spans


def read(run):
    calls = layer_spans.window_calls(run)
    if calls is None or not any(s.name == "upper-bound" and "prop_sweeps" in s.attrs
                                for c in calls for s in c):
        return None
    return layer_spans.mean_per_call(
        run, "upper-bound",
        lambda s: s.attrs.get("prop_sweeps", 0) + s.attrs.get("peel_sweeps", 0))
