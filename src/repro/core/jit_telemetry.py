"""Process-wide jit-recompile telemetry.

The streaming/temporal shape-stability story (pow2 padding, capacity
floors, fused while_loops) claims a whole replay compiles O(log) distinct
jit signatures. This module makes that claim measurable instead of
asserted: jax's monitoring stream emits one ``backend_compile`` duration
event per program XLA actually compiles, so the delta of
``compile_count()`` across a batch/step/replay IS the number of fresh
compiled signatures it minted (0 = every program was a cache hit).

The same event carries the compile DURATION (jax.monitoring calls the
listener as ``listener(event, duration_secs)``), so the listener also
accumulates ``compile_seconds()`` — the wall-clock XLA spent compiling —
and, when span tracing is live (repro.obs.trace), records each compile as
an ``xla.compile`` span ending at the current clock, which lands it inside
whatever engine span was open while the compile ran. That is how a trace
attributes "this batch was slow because it minted a fresh program" to the
exact batch/phase that paid for it. Every layer span (repro.obs.trace.layer)
also records the count's change across it as its ``compiles``, traced or
not.

The listener registers lazily on first use and is a no-op counter bump,
so leaving it installed costs nothing. Should jax stop emitting the
event, counts degrade to 0 rather than erroring — telemetry must never
take down the engine.
"""

from __future__ import annotations

_count = 0
_seconds = 0.0
_installed = False


def _on_duration(event: str, *args, **kwargs) -> None:
    global _count, _seconds
    if "backend_compile" in event:
        _count += 1
        dur = 0.0
        if args:
            try:
                dur = float(args[0])
            except (TypeError, ValueError):
                pass
        _seconds += dur
        try:
            from repro.obs import trace

            if trace.enabled():
                trace.record("xla.compile", dur, event=event)
        except Exception:
            pass  # tracing must never take down a compile


def install() -> None:
    """Register the compile listener once (idempotent)."""
    global _installed
    if _installed:
        return
    _installed = True
    try:
        import jax

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    except Exception:
        pass  # no monitoring API: compile_count() stays 0 forever


def compile_count() -> int:
    """Monotone count of XLA compilations since the listener installed.

    Diff two snapshots to count the recompiles a region of code caused.
    """
    install()
    return _count


def compile_seconds() -> float:
    """Monotone wall-clock seconds XLA spent compiling since install.

    Diff two snapshots to attribute compile time to a region of code —
    the duration-valued sibling of ``compile_count()`` (the listener
    always received the durations; it used to discard them).
    """
    install()
    return _seconds
