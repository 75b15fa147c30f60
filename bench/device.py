"""The chip the benchmark runs on: the check for it, its peaks, its memory.

Peaks are the published figures of the chip, keyed by jax's
``device_kind``; a device missing from the table is an error, never a
default. TPU v5e ("TPU v5 lite" to jax): Google Cloud documentation,
"TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB of HBM at 819 GB/s. The FLOP/s figures are the matrix unit's;
the k-core kernels do no matrix-unit arithmetic worth counting, so their
rooflines are bound by HBM bytes alone (see ``bench.work``).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int) -> list:
    """The first ``chips`` TPU devices, or ``NoChip``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax's devices are {devs[0].platform} ({len(devs)})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, jax sees {len(devs)}")
    return devs[:chips]


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[kind]


def summary(devs: list) -> dict:
    """The device as jax reports it, with the peak memory of the fullest chip."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
            "memory_peak_bytes": peak}
