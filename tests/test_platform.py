"""Platform-config layer (repro.platform): dispatch-mode vocabulary, env
plumbing, roofline peaks, and the forced-host-device-count lane (the env
mutation is backend-init-order sensitive, so the device-count assertions
run in subprocesses)."""

import os
import pathlib
import subprocess
import sys

import pytest

from repro import platform

# the checkout the subprocesses run from (they import src/ from here)
_REPO = pathlib.Path(__file__).resolve().parents[1]

_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": os.path.expanduser("~"),
        "JAX_PLATFORMS": "cpu"}


# ----------------------- dispatch-mode vocabulary ---------------------- #

@pytest.mark.parametrize("raw,want", [
    ("auto", "auto"), ("pallas", "pallas"), ("xla", "xla"),
    ("on", "pallas"), ("1", "pallas"), ("true", "pallas"),
    ("off", "xla"), ("0", "xla"), ("false", "xla"),
    ("  ON ", "pallas"), ("Off", "xla"),
])
def test_normalize_dispatch(raw, want):
    assert platform.normalize_dispatch(raw) == want


def test_normalize_dispatch_unknown_warns_and_defaults():
    with pytest.warns(RuntimeWarning, match="unknown dispatch mode"):
        assert platform.normalize_dispatch("vulkan") == "auto"


def test_dispatch_mode_priority(monkeypatch):
    """Override beats env beats the auto default."""
    monkeypatch.delenv(platform.ENV_DISPATCH, raising=False)
    platform.set_dispatch_mode(None)
    assert platform.dispatch_mode() == "auto"
    monkeypatch.setenv(platform.ENV_DISPATCH, "on")
    assert platform.dispatch_mode() == "pallas"
    platform.set_dispatch_mode("off")
    try:
        assert platform.dispatch_mode() == "xla"
    finally:
        platform.set_dispatch_mode(None)
    assert platform.dispatch_mode() == "pallas"


def test_set_platform_rejects_unknown():
    with pytest.raises(ValueError, match="platform must be one of"):
        platform.set_platform("abacus")


# ----------------------------- peaks ----------------------------------- #

def test_peaks_defaults_and_env_override(monkeypatch):
    monkeypatch.delenv(platform.ENV_PEAK_GFLOPS, raising=False)
    monkeypatch.delenv(platform.ENV_PEAK_GBS, raising=False)
    flops, bw = platform.peaks("TPU v5 lite")   # TPU v5e, Google Cloud docs
    assert flops == 197e12 and bw == 819e9
    monkeypatch.setenv(platform.ENV_PEAK_GFLOPS, "123")
    monkeypatch.setenv(platform.ENV_PEAK_GBS, "45")
    flops, bw = platform.peaks("TPU v5 lite")
    assert flops == 123e9 and bw == 45e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v99", None])
def test_peaks_unknown_device_kind_raises(kind):
    """No default row: a device without published peaks (the CPU the tests
    run on, for one) is an error, not the nearest guess."""
    with pytest.raises(KeyError, match="no published peaks"):
        platform.peaks(kind)


def test_summary_reports_resolved_state():
    s = platform.summary()
    assert s["platform"] == "cpu" and s["kind"] == "cpu"
    assert s["count"] >= 1
    assert s["dispatch_mode"] in ("auto", "pallas", "xla")
    assert s["interpret_kernels"] is True


# ------------------------ device checks -------------------------------- #

def test_interpret_kernels_by_backend(monkeypatch):
    """Interpret mode on CPU only; TPU compiles natively; any other
    backend raises instead of quietly interpreting the kernels."""
    import jax

    assert platform.interpret_kernels() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert platform.interpret_kernels() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="not on 'gpu'"):
        platform.interpret_kernels()


def test_require_platform_and_devices():
    platform.require_platform("cpu")
    with pytest.raises(RuntimeError, match="asked for platform 'tpu'"):
        platform.require_platform("tpu")
    assert len(platform.require_devices(1)) == 1
    with pytest.raises(RuntimeError, match="this host has"):
        platform.require_devices(10_000)


# ------------------------ compile cache -------------------------------- #

@pytest.fixture
def restore_cache_config():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_enable_compilation_cache)
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_enable_compilation_cache", saved[1])
    compilation_cache.reset_cache()


def test_compile_cache_default_is_fixed_checkout_path(monkeypatch,
                                                      restore_cache_config):
    import jax

    monkeypatch.delenv(platform.ENV_COMPILE_CACHE, raising=False)
    path = platform.enable_compile_cache()
    assert path == str(platform.COMPILE_CACHE_DIR)
    assert platform.COMPILE_CACHE_DIR.name == ".jax_cache"
    assert (platform.COMPILE_CACHE_DIR.parent / "src" / "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == path
    assert platform.enable_compile_cache() == path   # same path every call


def test_compile_cache_env_dir_wins(monkeypatch, restore_cache_config,
                                    tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it and the helper sets
    no directory of its own."""
    import jax

    monkeypatch.setenv(platform.ENV_COMPILE_CACHE, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert platform.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


# ----------------------- forced host device count ---------------------- #

def test_force_host_device_count_rewrites_flag(monkeypatch):
    monkeypatch.setenv(
        "XLA_FLAGS",
        "--xla_force_host_platform_device_count=2 --xla_foo=bar")
    import os

    import warnings
    with warnings.catch_warnings():
        # jax backends are already live in this test process — the warning
        # about late configuration is expected and not under test here
        warnings.simplefilter("ignore", RuntimeWarning)
        platform.force_host_device_count(8)
    flags = os.environ["XLA_FLAGS"].split()
    assert "--xla_force_host_platform_device_count=8" in flags
    assert "--xla_foo=bar" in flags
    assert sum(f.startswith("--xla_force_host_platform_device_count")
               for f in flags) == 1


def test_force_host_device_count_rejects_nonpositive():
    with pytest.raises(ValueError):
        platform.force_host_device_count(0)


def test_configure_from_env_forces_devices_subprocess():
    """REPRO_HOST_DEVICES=4 + configure_from_env() before backend init →
    jax sees 4 host devices (the CI forced-multi-device lane mechanism)."""
    script = (
        "import repro.platform as p\n"
        "applied = p.configure_from_env()\n"
        "assert applied == {'host_devices': 4}, applied\n"
        "import jax\n"
        "assert jax.device_count() == 4, jax.device_count()\n"
        "print('OK')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env={**_ENV, "REPRO_HOST_DEVICES": "4"}, cwd=_REPO,
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().endswith("OK")


def test_configure_from_env_noop_without_vars(monkeypatch):
    for var in (platform.ENV_PLATFORM, platform.ENV_HOST_DEVICES,
                platform.ENV_X64):
        monkeypatch.delenv(var, raising=False)
    assert platform.configure_from_env() == {}


# ----------------------------- host heap ------------------------------- #

# prints, for a 16 MiB array made before and after pin_host_heap(),
# whether it lies in the process's brk heap, and what the call returned
_HEAP_SCRIPT = (
    "import numpy as np\n"
    "import repro.platform as p\n"
    "def in_heap(a):\n"
    "    x = a.ctypes.data\n"
    "    for line in open('/proc/self/maps'):\n"
    "        if line.rstrip().endswith('[heap]'):\n"
    "            lo, hi = (int(v, 16) for v in line.split()[0].split('-'))\n"
    "            return lo <= x < hi\n"
    "    return False\n"
    "before = in_heap(np.ones(16 << 20, np.uint8))\n"
    "pinned = p.pin_host_heap(), p.pin_host_heap()\n"
    "after = in_heap(np.ones(16 << 20, np.uint8))\n"
    "print(before, pinned, after)\n"
)


def test_pin_host_heap_serves_staging_sizes_from_heap():
    """Unpinned, a 16 MiB array is mapped afresh; pinned, it comes from
    the heap, whose freed pages the next call reuses."""
    proc = subprocess.run([sys.executable, "-c", _HEAP_SCRIPT], capture_output=True,
                          text=True, env=_ENV, cwd=_REPO, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == ["False", "(True,", "True)", "True"]


@pytest.mark.parametrize("var,value", [
    ("MALLOC_MMAP_THRESHOLD_", "131072"),
    ("MALLOC_TRIM_THRESHOLD_", "131072"),
    ("GLIBC_TUNABLES", "glibc.malloc.mmap_threshold=131072"),
])
def test_pin_host_heap_leaves_a_tuned_allocator_alone(var, value):
    proc = subprocess.run([sys.executable, "-c", _HEAP_SCRIPT], capture_output=True,
                          text=True, env={**_ENV, var: value}, cwd=_REPO, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == ["False", "(False,", "False)", "False"]


def test_graph_staging_pins_host_heap(monkeypatch):
    import numpy as np

    from repro.core import dispatch

    calls = []
    monkeypatch.setattr(platform, "pin_host_heap", lambda: calls.append(1) or True)
    src, dst = np.array([0, 1, 1, 2], np.int32), np.array([1, 0, 2, 1], np.int32)
    dispatch._stage(dispatch.DispatchPlan(kind="xla"), src, dst, 3, None)
    assert calls == [1]
