"""The main-path TPU programs compile for a v5e chip that is described, not
attached: the TPU compiler refuses here what it would refuse on the chip
(Mosaic's operand types, tile alignment, fast-memory limits), at no chip
time. Nothing runs, so nothing here says anything about results or speed.

The topology is described inside a module-scoped fixture — never at import
or in a skip condition — and every compile runs in the test's own process
with the persistent compilation cache off.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import platform
from repro.core import dispatch
from repro.core.kcore import _bs_iters
from repro.kernels.kcore_hindex.kernel import hindex_rows_pallas
from repro.kernels.kcore_hindex.ops import _pick_row_tile
from repro.kernels.segment_sum.kernel import segment_sum_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_tpu(monkeypatch):
    """Compile the kernels natively (the CPU backend would interpret them)
    and keep these compiles out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(platform, "interpret_kernels", lambda: False)
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("width", [2048, 28032])
def test_hindex_kernel_compiles(one_chip, for_tpu, width):
    """Widths of the 2048 bucket and of the web-Google hub bucket."""
    tile = _pick_row_tile(width)
    rows = 4 * tile

    def kernel(nbr, est):
        return hindex_rows_pallas(nbr, est, n_iters=16, row_tile=tile, interpret=False)

    compiled = (
        jax.jit(kernel).lower(_spec(one_chip, (rows, width)), _spec(one_chip, (rows, 1))).compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_segment_sum_kernel_compiles(one_chip, for_tpu):
    R, be, blocks = 256, 512, 8
    compiled = (
        jax.jit(lambda v, r, b: segment_sum_pallas(v, r, b, 4, R=R, interpret=False))
        .lower(
            _spec(one_chip, (blocks, be // 128, 128), jnp.float32),
            _spec(one_chip, (blocks, be // 128, 128)),
            _spec(one_chip, (blocks,)),
        )
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_fused_convergence_compiles(one_chip, for_tpu):
    """The default TPU route of a from-scratch decomposition — the fused
    while_loop with the ELL h-index and blocked segment-sum kernels — at a
    mid-size shape: 2^18 vertices, 2^21 arcs, max degree 9000."""
    n, arcs, max_deg = 1 << 18, 1 << 21, 9000
    buckets = [
        (8, 80000), (32, 20000), (128, 6000), (512, 1500), (2048, 200), (8192, 16), (9088, 8)
    ]
    e_pad = arcs + n  # one half-filled 2048-edge block per 1024-row block
    S = lambda shape, dtype=jnp.int32: _spec(one_chip, shape, dtype)  # noqa: E731
    ops = dispatch.GraphOperands(
        S((arcs,)),
        S((arcs,)),
        (S((e_pad // 2048, 16, 128)), S((e_pad // 2048, 16, 128)), S((e_pad // 2048,))),
        tuple((S((rows,)), S((rows, w))) for w, rows in buckets),
    )
    compiled = dispatch._fused_jit.lower(
        ops,
        S((n,)),
        S((arcs,), jnp.bool_),
        S((n,), jnp.bool_),
        S((n,)),
        n=n,
        n_iters=_bs_iters(max_deg),
        max_rounds=n + 1,
        R=1024,
        n_rows_pad=n,
    ).compile()
    text = compiled.as_text()
    # one h-index kernel per bucket, plus the receivers' segment sum
    assert text.count("tpu_custom_call") >= len(buckets) + 1
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_kernels_and_round_scopes_keep_their_names_on_tpu(one_chip, for_tpu):
    """The trace names each kernel call after its ``pallas_call`` and each
    part of the round after its ``jax.named_scope``: the h-index kernel is
    ``kcore_hindex.N``, the segment sum ``segment_sum.N``, and every scope
    of the round body is in the ops' metadata."""
    n, arcs = 1 << 12, 1 << 15
    e_pad = arcs + n
    S = lambda shape, dtype=jnp.int32: _spec(one_chip, shape, dtype)  # noqa: E731
    ops = dispatch.GraphOperands(
        S((arcs,)),
        S((arcs,)),
        (S((e_pad // 2048, 16, 128)), S((e_pad // 2048, 16, 128)), S((e_pad // 2048,))),
        tuple((S((rows,)), S((rows, w))) for w, rows in ((8, 2000), (128, 100))),
    )
    text = dispatch._fused_jit.lower(
        ops, S((n,)), S((arcs,), jnp.bool_), S((n,), jnp.bool_), S((n,)),
        n=n, n_iters=8, max_rounds=n + 1, R=1024, n_rows_pad=n,
    ).compile().as_text()
    calls = [ln.strip().split(" = ", 1)[0] for ln in text.splitlines()
             if "tpu_custom_call" in ln and " = " in ln]
    assert calls and all(c.startswith(("%kcore_hindex.", "%segment_sum.")) for c in calls)
    assert {c.split(".")[0] for c in calls} == {"%kcore_hindex", "%segment_sum"}
    for scope in ("kcore.gather", "kcore.hindex", "kcore.changed", "kcore.recv", "kcore.stats"):
        assert f"/{scope}/" in text, scope
