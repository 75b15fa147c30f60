"""The program's layer spans of the window's calls.

The program keeps its finished layer spans (``repro.obs.trace.layer``) in a
bounded in-memory ring, each with its parent, its wall, the XLA compiles
it paid for and its attributes. One call of a cell is one root layer span
(``kcore.decompose`` in a decompose cell, ``batch`` in a churn cell), so
the window's calls are the last ``len(run.steps)`` roots in the ring, and
every layer span that finished under one belongs to that call.
"""

from __future__ import annotations


def window_calls(run) -> list | None:
    """One list per timed call, in order: the call's root layer span, then
    every layer span that finished under it. None where the program keeps
    no ring of layer spans, or where the ring no longer holds them all."""
    try:
        from repro.obs import trace
    except ImportError:
        return None
    recent = getattr(trace, "recent_layers", None)
    if recent is None or not run.steps:
        return None
    spans = recent()
    roots = [s for s in spans if s.parent is None][-len(run.steps):]
    if len(roots) < len(run.steps):
        return None
    calls = {id(r): [r] for r in roots}
    for s in spans:
        top = s
        while top.parent is not None:
            top = top.parent
        if top is not s and id(top) in calls:
            calls[id(top)].append(s)
    out = [calls[id(r)] for r in roots]
    if any(len(c) != 1 + c[0].descendants for c in out):
        return None
    return out


def mean_per_call(run, name: str, value) -> float | None:
    """Mean over the window's calls of ``value(span)`` summed over the
    layer spans called ``name`` in each call (0 in a call without one)."""
    calls = window_calls(run)
    if calls is None:
        return None
    return sum(sum(value(s) for s in c if s.name == name) for c in calls) / len(calls)
