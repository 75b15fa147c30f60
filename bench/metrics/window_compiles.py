"""XLA compiles inside the measured window
(``repro.core.jit_telemetry.compile_count`` at close minus at open)."""


def read(run):
    return float(run.window_compiles) if run.steps else None
