"""Nested-span tracer with Chrome ``trace_event`` export.

The paper's whole contribution is *measurement* — running time and message
traffic of the distributed decomposition — so the repo's hot paths carry
spans: the host round loop (core/kcore.py), the fused convergence runtime
(core/runtime.py), the streaming engine's batch phases (patch / seed /
converge / host-reconstruct), window advances (temporal/window.py), and
the serving loop. XLA compile durations are attributed to the enclosing
span by repro.core.jit_telemetry (``xla.compile`` spans).

Two kinds of span, each nesting per thread:

  * **Detail spans** (``span``): rounds, window diffs, requests. Off until
    ``enable()``; the disabled path returns the shared ``NULL_SPAN``.
  * **Layer spans** (``layer``): the few boundaries between layers —
    ``kcore.decompose``, ``batch`` and its phases, ``fused-converge`` ->
    ``device-converge`` -> ``stage`` / ``device-loop``,
    ``stats-reconstruct``. Always timed: each takes two clock reads,
    records its parent layer span and the XLA compiles it paid for
    (``compiles``), and lands on exit in a bounded in-memory ring
    (``recent_layers()``) that the engines' phase timings and the
    benchmark's per-layer metrics read. While one is open it also sits in
    the ``jax.profiler`` host plane as a ``TraceAnnotation`` of the same
    name (once jax is imported), on the clock of the device ops. With
    tracing enabled it is exported like a detail span too.

Design constraints, in order:

  1. **Zero cost when disabled.** Every engine keeps its detail spans in
     place permanently; the disabled path is one attribute check returning
     a shared no-op span. No timestamps are taken, nothing allocates per
     span, and CI's perf gates run with tracing off. Layer spans cost a
     few microseconds each, against calls that run for milliseconds.
  2. **Dependency-free.** stdlib only (``time``, ``threading``, ``json``)
     — the tracer must be importable before jax, from the validator CLI,
     and from any future subprocess worker. It touches jax only through
     ``sys.modules``, once something else has imported it.
  3. **Thread-safe.** Spans nest per thread (a ``threading.local`` stack);
     the finished-event list is lock-protected. Concurrent serving threads
     each get a coherent span tree under their own ``tid``.

Export is the Chrome ``trace_event`` JSON array-of-complete-events format
(``ph: "X"``): load the file in Perfetto (https://ui.perfetto.dev) or
``chrome://tracing`` to see the nested flame graph. Timestamps come from
``time.perf_counter_ns`` (monotonic), reported in microseconds.

API sketch (module-level functions drive one process-wide default tracer)::

    from repro.obs import trace

    trace.enable()
    with trace.span("batch", graph="EEN") as sp:
        with trace.span("patch"):
            ...
        sp.set(rounds=3, messages=1234)      # attach attrs any time
    trace.export("out.json")                 # open in Perfetto

    with trace.layer("stage") as st:         # timed whether enabled or not
        ...
        st.add("h2d_bytes", 4096)
    st.seconds, st.compiles, trace.recent_layers()[-1] is st
"""

from __future__ import annotations

import collections
import json
import os
import sys
import threading
import time

# finished layer spans kept in memory: a benchmark window of a few dozen
# calls of ~10 layer spans each, many times over
LAYER_RING = 4096


class _NullSpan:
    """Shared no-op span returned while tracing is disabled."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "_NullSpan":
        return self


NULL_SPAN = _NullSpan()


class Span:
    """One live span: a context manager that records a complete event."""

    __slots__ = ("_tracer", "name", "attrs", "_t0")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs

    def set(self, **attrs) -> "Span":
        """Attach attributes to this span (shows up under ``args``)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        self._tracer._stack().append(self)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        self._tracer._emit(self.name, self._t0, t1 - self._t0, self.attrs)
        return False


def _compile_count() -> int:
    """``repro.core.jit_telemetry.compile_count()`` once jax is imported
    (nothing compiles before that)."""
    if "jax" not in sys.modules:
        return 0
    from repro.core.jit_telemetry import compile_count

    return compile_count()


class LayerSpan:
    """One always-timed span at a layer boundary (``Tracer.layer``).

    ``seconds`` is its wall (up to now while it is open), ``parent`` the
    layer span that was open on this thread when it started, ``compiles``
    the XLA compiles that ran inside it, ``children_s`` the summed seconds
    of its finished direct children by name, ``descendants`` how many
    layer spans finished under it, and ``attrs`` what the code attached
    (``h2d_bytes`` on ``stage`` spans).
    """

    __slots__ = ("_tracer", "name", "attrs", "parent", "tid", "t0_ns",
                 "t1_ns", "compiles", "children_s", "descendants",
                 "_compiles0", "_annotation", "_traced")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.parent: LayerSpan | None = None
        self.t1_ns: int | None = None
        self.compiles = 0
        self.children_s: dict[str, float] = {}
        self.descendants = 0
        self._annotation = None

    def set(self, **attrs) -> "LayerSpan":
        """Attach attributes to this span."""
        self.attrs.update(attrs)
        return self

    def add(self, key: str, amount: int) -> "LayerSpan":
        """Add ``amount`` to the numeric attribute ``key`` (from 0)."""
        self.attrs[key] = self.attrs.get(key, 0) + amount
        return self

    @property
    def seconds(self) -> float:
        end = self.t1_ns if self.t1_ns is not None else time.perf_counter_ns()
        return (end - self.t0_ns) / 1e9

    def event(self) -> dict:
        """This finished span as a Chrome complete event."""
        return _chrome_event(self.name, self.t0_ns, self.t1_ns - self.t0_ns,
                             self.tid, dict(self.attrs, compiles=self.compiles))

    def __enter__(self) -> "LayerSpan":
        tracer = self._tracer
        layers = tracer._layer_stack()
        self.parent = layers[-1] if layers else None
        layers.append(self)
        self._traced = tracer._enabled
        if self._traced:
            tracer._stack().append(self)
        jax = sys.modules.get("jax")
        if jax is not None:
            self._annotation = jax.profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        self.tid = threading.get_ident()
        self._compiles0 = _compile_count()
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1_ns = time.perf_counter_ns()
        self.compiles = _compile_count() - self._compiles0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        tracer = self._tracer
        _pop(tracer._layer_stack(), self)
        parent = self.parent
        if parent is not None:
            parent.children_s[self.name] = (
                parent.children_s.get(self.name, 0.0) + self.seconds)
            parent.descendants += 1 + self.descendants
        with tracer._lock:
            tracer._layers.append(self)
        if self._traced:
            _pop(tracer._stack(), self)
            tracer._emit_event(self.event())
        return False


def _pop(stack: list, span) -> None:
    if stack and stack[-1] is span:
        stack.pop()


def _chrome_event(name: str, t0_ns: int, dur_ns: int, tid: int,
                  attrs: dict) -> dict:
    ev = {
        "name": name,
        "ph": "X",
        "ts": t0_ns / 1e3,          # Chrome wants microseconds
        "dur": max(dur_ns, 0) / 1e3,
        "pid": os.getpid(),
        "tid": tid,
    }
    if attrs:
        ev["args"] = dict(attrs)
    return ev


class Tracer:
    """A span recorder. Most callers use the module-level default tracer."""

    def __init__(self):
        self._enabled = False
        self._events: list[dict] = []
        self._layers: collections.deque = collections.deque(maxlen=LAYER_RING)
        self._lock = threading.Lock()
        self._tls = threading.local()

    # ------------------------------------------------------------------ #
    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self) -> None:
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    def reset(self) -> None:
        """Drop every recorded event and finished layer span (keeps the
        enabled flag)."""
        with self._lock:
            self._events = []
            self._layers.clear()

    # ------------------------------------------------------------------ #
    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _layer_stack(self) -> list:
        stack = getattr(self._tls, "layers", None)
        if stack is None:
            stack = self._tls.layers = []
        return stack

    def _emit(self, name: str, t0_ns: int, dur_ns: int, attrs: dict) -> None:
        self._emit_event(_chrome_event(name, t0_ns, dur_ns,
                                       threading.get_ident(), attrs))

    def _emit_event(self, ev: dict) -> None:
        with self._lock:
            self._events.append(ev)

    # ------------------------------------------------------------------ #
    def span(self, name: str, **attrs):
        """Context manager for one nested span (no-op while disabled)."""
        if not self._enabled:
            return NULL_SPAN
        return Span(self, name, attrs)

    def layer(self, name: str, **attrs) -> LayerSpan:
        """Context manager for one layer span, timed whether or not tracing
        is enabled (see the module docstring)."""
        return LayerSpan(self, name, attrs)

    def recent_layers(self) -> list[LayerSpan]:
        """The finished layer spans still in the ring, oldest first."""
        with self._lock:
            return list(self._layers)

    def current(self) -> Span | LayerSpan | None:
        """The innermost open span on this thread, if any."""
        stack = self._stack()
        return stack[-1] if stack else None

    def annotate(self, **attrs) -> None:
        """Attach attributes to the innermost open span (no-op otherwise)."""
        if not self._enabled:
            return
        cur = self.current()
        if cur is not None:
            cur.set(**attrs)

    def record(self, name: str, dur_s: float, **attrs) -> None:
        """Record an already-elapsed duration as a span ending *now*.

        For externally measured work (XLA compile durations from
        jax.monitoring) where only the duration is known: the span is
        synthesized as ending at the current clock, so it lands inside
        whatever span was open while the work ran.
        """
        if not self._enabled:
            return
        dur_ns = max(int(dur_s * 1e9), 0)
        self._emit(name, time.perf_counter_ns() - dur_ns, dur_ns, attrs)

    # ------------------------------------------------------------------ #
    def events(self) -> list[dict]:
        """A snapshot copy of every finished event."""
        with self._lock:
            return [dict(e) for e in self._events]

    def chrome_trace(self) -> dict:
        """The Chrome ``trace_event`` document (Perfetto-loadable)."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def export(self, path: str) -> str:
        """Write the Chrome trace JSON to ``path``; returns the path."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


# ---------------------------------------------------------------------- #
# Process-wide default tracer — what the engines instrument against.
# ---------------------------------------------------------------------- #

_DEFAULT = Tracer()


def get_tracer() -> Tracer:
    return _DEFAULT


def enabled() -> bool:
    return _DEFAULT.enabled


def enable() -> None:
    _DEFAULT.enable()


def disable() -> None:
    _DEFAULT.disable()


def reset() -> None:
    _DEFAULT.reset()


def span(name: str, **attrs):
    return _DEFAULT.span(name, **attrs)


def layer(name: str, **attrs) -> LayerSpan:
    return _DEFAULT.layer(name, **attrs)


def recent_layers() -> list[LayerSpan]:
    return _DEFAULT.recent_layers()


def current() -> Span | LayerSpan | None:
    return _DEFAULT.current()


def annotate(**attrs) -> None:
    _DEFAULT.annotate(**attrs)


def record(name: str, dur_s: float, **attrs) -> None:
    _DEFAULT.record(name, dur_s, **attrs)


def events() -> list[dict]:
    return _DEFAULT.events()


def chrome_trace() -> dict:
    return _DEFAULT.chrome_trace()


def export(path: str) -> str:
    return _DEFAULT.export(path)
