"""Computation-platform configuration layer.

One place that decides WHERE the supersteps run and HOW the Pallas kernels
are dispatched, driven by a flag or an environment variable — so the same
entry points cover a laptop CPU, a forced-multi-device CI lane, and a TPU:

* ``set_platform("cpu"|"tpu")`` — pick the jax platform.
* ``force_host_device_count(n)`` — expose ``n`` host (CPU) devices via
  ``--xla_force_host_platform_device_count``, turning a single machine into
  an in-process mesh for the sharded/fused-sharded paths. Must run before
  jax initializes its backends. ``prepare_mesh(n)`` applies it only when
  the selected platform is the CPU: a TPU mesh is made of chips.
* ``configure_from_env()`` — apply both from ``REPRO_PLATFORM`` /
  ``REPRO_HOST_DEVICES`` (+ ``REPRO_X64``); idempotent and cheap, called by
  the CLIs and ``tests/conftest.py`` so one exported variable reconfigures
  every entry point.
* ``require_platform(name)`` / ``require_devices(n)`` — after backend init,
  fail loudly when the run is not on the platform or device count it asked
  for, instead of running somewhere else. ``configure_run`` strings the
  steps together for the CLIs.
* ``dispatch_mode()`` — the Pallas kernel-dispatch switch (``REPRO_PALLAS``
  = ``auto`` | ``on``/``pallas`` | ``off``/``xla``) consumed by
  ``repro.core.dispatch``: ``auto`` routes the superstep h-index /
  segment-sum to the Pallas kernels on TPU and to the XLA segment ops on
  CPU, ``on`` forces the kernels (interpret mode on CPU — exact, slow; the
  parity/CI path), ``off`` keeps the plain XLA segment ops.
* ``interpret_kernels()`` — True on the CPU backend (tests), False on TPU;
  any other backend is an error.
* ``peaks()`` — published peak FLOP/s and bytes/s per ``device_kind`` for
  roofline reporting (``REPRO_PEAK_GFLOPS`` / ``REPRO_PEAK_GBS`` override);
  a device without a row raises.
* ``enable_compile_cache()`` — JAX's persistent compilation cache at one
  fixed directory, or wherever ``JAX_COMPILATION_CACHE_DIR`` says.
* ``pin_host_heap()`` — fix glibc malloc's mmap and trim thresholds, so
  the arc-sized temporaries of host staging are reused from the heap
  instead of being mapped and faulted in afresh on some calls.

Everything here touches only ``os.environ`` and ``jax.config`` (and, in
``pin_host_heap``, the C allocator) until a function documents otherwise — importing this module never initializes a
jax backend, so it is always safe to import first and configure before the
rest of the process touches a device.
"""

from __future__ import annotations

import os
import pathlib
import warnings

ENV_PLATFORM = "REPRO_PLATFORM"
ENV_HOST_DEVICES = "REPRO_HOST_DEVICES"
ENV_DISPATCH = "REPRO_PALLAS"
ENV_X64 = "REPRO_X64"
ENV_PEAK_GFLOPS = "REPRO_PEAK_GFLOPS"
ENV_PEAK_GBS = "REPRO_PEAK_GBS"
ENV_COMPILE_CACHE = "JAX_COMPILATION_CACHE_DIR"

_PLATFORMS = ("cpu", "tpu")

_FORCE_DEVICES_FLAG = "--xla_force_host_platform_device_count"

# the checkout root (src/repro/platform.py -> ../..): the compile cache
# lives at one fixed path inside it, so every run of this checkout hits it
_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
COMPILE_CACHE_DIR = _CHECKOUT / ".jax_cache"

# (peak FLOP/s, peak HBM bytes/s) per jax ``device_kind``, for roofline
# REPORTING only — never used for correctness or dispatch decisions.
# "TPU v5 lite" is TPU v5e: Google Cloud documentation, "TPU v5e"
# (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s.
_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
}

_DISPATCH_MODES = ("auto", "pallas", "xla")
_dispatch_override: str | None = None


# ---------------------------------------------------------------------- #
# Platform / device-count selection
# ---------------------------------------------------------------------- #


def set_platform(platform: str) -> None:
    """Select the jax platform (cpu/tpu). Call before backend init."""
    if platform not in _PLATFORMS:
        raise ValueError(f"platform must be one of {_PLATFORMS}, got {platform!r}")
    import jax

    jax.config.update("jax_platforms", platform)


def selected_platform() -> str | None:
    """The platform this process asked for before backend init, if any:
    ``REPRO_PLATFORM``, else the first entry of ``JAX_PLATFORMS``."""
    import jax

    chosen = os.environ.get(ENV_PLATFORM, "").strip().lower() or (jax.config.jax_platforms or "")
    return chosen.split(",")[0].strip() or None


def force_host_device_count(n: int) -> None:
    """Expose ``n`` host (CPU) devices to jax — the forced-multi-device lane.

    Rewrites any existing ``--xla_force_host_platform_device_count`` in
    ``XLA_FLAGS`` instead of appending a duplicate, so repeated calls (or a
    CLI flag on top of an exported variable) keep a single source of truth.
    The flag is read when jax initializes its backends; calling this after
    devices exist has no effect on the live process (jax caches backends),
    so configure first — the CLIs and conftest do.
    """
    n = int(n)
    if n < 1:
        raise ValueError(f"device count must be >= 1, got {n}")
    flags = os.environ.get("XLA_FLAGS", "").split()
    parts = [p for p in flags if not p.startswith(_FORCE_DEVICES_FLAG)]
    parts.append(f"{_FORCE_DEVICES_FLAG}={n}")
    os.environ["XLA_FLAGS"] = " ".join(parts)
    if _backends_initialized():
        warnings.warn(
            "force_host_device_count called after jax backends initialized; "
            "the new count only affects fresh processes",
            RuntimeWarning,
            stacklevel=2,
        )


def prepare_mesh(n: int) -> None:
    """Before backend init, for an ``n``-device mesh: on the CPU platform
    expose ``n`` host devices; anywhere else the mesh is built from the
    real devices, and ``require_devices`` refuses a host with too few."""
    if selected_platform() == "cpu":
        force_host_device_count(n)


def _backends_initialized() -> bool:
    """Has this process already materialized jax devices?"""
    import sys

    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return bool(xla_bridge._backends)


def configure_from_env() -> dict:
    """Apply ``REPRO_PLATFORM`` / ``REPRO_HOST_DEVICES`` / ``REPRO_X64``.

    Returns the subset of settings that were applied (empty when no
    variable is set). Safe to call repeatedly and from conftest — it only
    mutates ``os.environ`` / ``jax.config``, never initializes a backend.
    """
    applied: dict = {}
    platform = os.environ.get(ENV_PLATFORM, "").strip().lower()
    if platform:
        set_platform(platform)
        applied["platform"] = platform
    ndev = os.environ.get(ENV_HOST_DEVICES, "").strip()
    if ndev:
        force_host_device_count(int(ndev))
        applied["host_devices"] = int(ndev)
    x64 = os.environ.get(ENV_X64, "").strip().lower()
    if x64:
        import jax

        jax.config.update("jax_enable_x64", x64 in ("1", "true", "yes", "on"))
        applied["x64"] = x64 in ("1", "true", "yes", "on")
    return applied


def configure_run(
    *, platform: str | None = None, devices: int = 0, dispatch: str | None = None, mesh: int = 0
) -> None:
    """What a CLI run does before its first compile: the env-driven config
    and its flags (all before backend init), then the device checks
    (``--platform`` and ``--mesh`` must be met, or the run fails), then the
    compile cache."""
    configure_from_env()
    if platform:
        set_platform(platform)
    if devices:
        force_host_device_count(devices)
    if dispatch:
        set_dispatch_mode(dispatch)
    if mesh:
        prepare_mesh(mesh)
    if platform:
        require_platform(platform)
    if mesh:
        require_devices(mesh)
    enable_compile_cache()


def require_platform(platform: str) -> None:
    """Fail unless jax's first device is on ``platform`` (initializes the
    backend). A run that asked for a TPU never continues on the CPU."""
    import jax

    found = jax.devices()[0].platform
    if found != platform:
        raise RuntimeError(
            f"asked for platform {platform!r}, but jax's first device is on {found!r}"
        )


def require_devices(n: int) -> list:
    """The first ``n`` devices of the default backend, or an error naming
    what the host has (initializes the backend)."""
    import jax

    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"a {n}-device mesh needs {n} devices; "
            f"this host has {len(devs)} {devs[0].platform} device(s)"
        )
    return devs[:n]


# ---------------------------------------------------------------------- #
# Pallas kernel dispatch mode
# ---------------------------------------------------------------------- #


def normalize_dispatch(mode: str) -> str:
    """Map accepted spellings to the canonical auto/pallas/xla vocabulary."""
    m = mode.strip().lower()
    aliases = {
        "on": "pallas",
        "1": "pallas",
        "true": "pallas",
        "off": "xla",
        "0": "xla",
        "false": "xla",
    }
    m = aliases.get(m, m)
    if m not in _DISPATCH_MODES:
        warnings.warn(
            f"unknown dispatch mode {mode!r} (want auto/on/off); using auto",
            RuntimeWarning,
            stacklevel=2,
        )
        return "auto"
    return m


def dispatch_mode() -> str:
    """Current kernel-dispatch mode: auto | pallas | xla.

    Priority: ``set_dispatch_mode()`` override (CLI flags), then the
    ``REPRO_PALLAS`` environment variable, then ``auto``.
    """
    if _dispatch_override is not None:
        return _dispatch_override
    return normalize_dispatch(os.environ.get(ENV_DISPATCH, "auto"))


def set_dispatch_mode(mode: str | None) -> None:
    """Process-wide dispatch override (None restores env/auto behavior)."""
    global _dispatch_override
    _dispatch_override = None if mode is None else normalize_dispatch(mode)


def interpret_kernels() -> bool:
    """Should Pallas kernels run in interpret mode?

    Only on the CPU backend, where the tests run them; on TPU they compile
    natively. Any other backend has no route for the kernels and raises
    rather than quietly interpreting them.
    """
    import jax

    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run on TPU (native) or CPU (interpreted), not on {backend!r}"
    )


# ---------------------------------------------------------------------- #
# Roofline peaks / summary
# ---------------------------------------------------------------------- #


def peaks(device_kind: str | None = None) -> tuple[float, float]:
    """(peak FLOP/s, peak bytes/s) of ``device_kind`` (default: jax's first
    device), with ``REPRO_PEAK_GFLOPS`` / ``REPRO_PEAK_GBS`` overrides.
    Raises KeyError for a device without published peaks."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    if device_kind not in _PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: {sorted(_PEAKS)}"
        )
    flops, membw = _PEAKS[device_kind]
    gflops = os.environ.get(ENV_PEAK_GFLOPS, "").strip()
    gbs = os.environ.get(ENV_PEAK_GBS, "").strip()
    if gflops:
        flops = float(gflops) * 1e9
    if gbs:
        membw = float(gbs) * 1e9
    return flops, membw


def device_summary() -> dict:
    """The device as jax reports it: platform, kind and count (initializes
    backends). Every chip result names its device with these keys."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def summary() -> dict:
    """The resolved platform state (for CLI reports; initializes backends)."""
    return {
        **device_summary(),
        "dispatch_mode": dispatch_mode(),
        "interpret_kernels": interpret_kernels(),
    }


# ---------------------------------------------------------------------- #
# Persistent compilation cache
# ---------------------------------------------------------------------- #


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no other directory. Otherwise the cache lives at the fixed
    ``.jax_cache/`` of this checkout: the path is part of what a later run
    must find again. Call before the first compile; tests never call it,
    so their compiles stay out of the cache.
    """
    import jax

    path = os.environ.get(ENV_COMPILE_CACHE, "").strip()
    if not path:
        path = str(COMPILE_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path


# ---------------------------------------------------------------------- #
# Host heap
# ---------------------------------------------------------------------- #

# glibc's mallopt parameters and the environment variables that set them
_M_TRIM_THRESHOLD, _M_MMAP_THRESHOLD = -1, -3
_MALLOC_ENV = ("MALLOC_MMAP_THRESHOLD_", "MALLOC_TRIM_THRESHOLD_", "GLIBC_TUNABLES")
HEAP_MMAP_THRESHOLD = 1 << 30
HEAP_TRIM_THRESHOLD = (1 << 31) - 1  # mallopt takes an int
_heap_pinned: bool | None = None


def pin_host_heap() -> bool:
    """Serve host allocations below 1 GiB from the heap and keep up to
    2 GiB of freed heap; returns whether the thresholds are pinned.

    Host staging (``build_ell``, ``blocked_layout``, the slot arrays)
    allocates and frees arc-sized temporaries on every call. glibc's
    default moves its mmap threshold with the sizes it has freed, so some
    calls reuse heap pages and others map fresh ones and fault every page
    in: one TPU v5e host measured 29-38 ms a call pinned against 34-68 ms
    unpinned, per process bimodal, for the same graph. Idempotent; leaves
    the allocator alone where the environment already tunes it or the C
    library is not glibc.
    """
    global _heap_pinned
    if _heap_pinned is None:
        _heap_pinned = False
        if not any(k in os.environ for k in _MALLOC_ENV):
            import ctypes

            try:
                mallopt = ctypes.CDLL(None).mallopt
            except (OSError, AttributeError):
                return False
            mallopt.argtypes = (ctypes.c_int, ctypes.c_int)
            # a threshold set explicitly also turns glibc's dynamic one off
            _heap_pinned = bool(
                mallopt(_M_MMAP_THRESHOLD, HEAP_MMAP_THRESHOLD)
                and mallopt(_M_TRIM_THRESHOLD, HEAP_TRIM_THRESHOLD)
            )
    return _heap_pinned
