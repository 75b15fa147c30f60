"""The main-path TPU programs compile for a v5e chip that is described, not
attached: the TPU compiler refuses here what it would refuse on the chip
(Mosaic's operand types, tile alignment, fast-memory limits), at no chip
time. Nothing runs, so nothing here says anything about results or speed.

The topology is described inside a module-scoped fixture — never at import
or in a skip condition — and every compile runs in the test's own process
with the persistent compilation cache off.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import platform
from repro.core import dispatch
from repro.core.kcore import _bs_iters
from repro.kernels.kcore_hindex.kernel import hindex_rows_pallas
from repro.kernels.kcore_hindex.ops import _pick_row_tile
from repro.kernels.segment_sum.kernel import row_hits_pallas, segment_sum_pallas


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


@pytest.mark.parametrize("R,be", [(1024, 2048), (128, 128)])
def test_row_hits_kernel_compiles(one_chip, for_tpu, R, be):
    """The round's tiles (R=1024, be=2048), and the smallest: one sublane
    of probes per row block."""
    blocks, out_blocks = 8, 4
    compiled = (
        jax.jit(lambda e, r, b, p: row_hits_pallas(e, r, b, p, R=R, interpret=False))
        .lower(
            _spec(one_chip, (blocks, be // 128, 128)),
            _spec(one_chip, (blocks, be // 128, 128)),
            _spec(one_chip, (blocks,)),
            _spec(one_chip, (out_blocks, R // 128, 128)),
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
        S((e_pad,)),
        S((e_pad,)),
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


@pytest.mark.parametrize("route,buckets,kernels", [
    ("ell", ((8, 2000), (128, 100)), {"%kcore_hindex", "%segment_sum"}),
    ("slots", (), {"%row_hits", "%segment_sum"}),
], ids=["ell", "slots"])
def test_kernels_and_round_scopes_keep_their_names_on_tpu(one_chip, for_tpu, route, buckets, kernels):
    """The trace names each kernel call after its ``pallas_call`` and each
    part of the round after its ``jax.named_scope``: on the ELL route (a
    decomposition) the h-index kernel is ``kcore_hindex.N``, on the slot
    route (a churn batch) the binary search's hit count is ``row_hits.N``,
    the receivers' count is ``segment_sum.N`` on both, and every scope of
    the fused program is in the ops' metadata."""
    n, arcs = 1 << 12, 1 << 15
    e_pad = arcs + n
    S = lambda shape, dtype=jnp.int32: _spec(one_chip, shape, dtype)  # noqa: E731
    ops = dispatch.GraphOperands(
        S((e_pad,)),
        S((e_pad,)),
        (S((e_pad // 2048, 16, 128)), S((e_pad // 2048, 16, 128)), S((e_pad // 2048,))),
        tuple((S((rows,)), S((rows, w))) for w, rows in buckets),
    )
    text = dispatch._fused_jit.lower(
        ops, S((n,)), S((arcs,), jnp.bool_), S((n,), jnp.bool_), S((n,)),
        n=n, n_iters=8, max_rounds=n + 1, R=1024, n_rows_pad=n,
    ).compile().as_text()
    calls = [ln.strip().split(" = ", 1)[0] for ln in text.splitlines()
             if "tpu_custom_call" in ln and " = " in ln]
    assert {c.split(".")[0] for c in calls} == kernels, calls
    for scope in ("kcore.mask", "kcore.gather", "kcore.hindex", "kcore.changed", "kcore.recv",
                  "kcore.stats"):
        assert f"/{scope}/" in text, scope


def _gathers(text):
    """(op_name, element count of the gathered operand, element count of
    the result) of every gather in compiled HLO text."""
    sizes = {}
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]", ln)
        if m:
            sizes[m.group(1)] = math.prod(int(d) for d in m.group(2).split(",") if d)
    out = []
    for ln in text.splitlines():
        m = re.search(r"(%[\w.\-]+) = \S+ gather\((%[\w.\-]+),", ln)
        if m:
            name = re.search(r'op_name="([^"]*)"', ln).group(1)
            out.append((name, sizes[m.group(2)], sizes[m.group(1)]))
    return out


@pytest.mark.parametrize("n,arcs,e_pad", [
    (1 << 14, 1 << 19, 540_672),        # graph500-s14.churn
    (1 << 16, 1 << 21, 2_158_592),      # graph500-s16.trickle
], ids=["churn", "trickle"])
def test_round_counts_read_no_arc_sized_gather_on_tpu(one_chip, for_tpu, n, arcs, e_pad):
    """At the churn and trickle cells' shapes (vertices, padded arc slots,
    padded layout slots; 16 bsearch steps) the masks the fused round counts
    are built in the blocked layout's slot order: inside the while body no
    gather under ``kcore.hindex`` or ``kcore.recv`` reads an arc-sized
    operand, only vertex-sized ones, and none under ``kcore.hindex`` makes
    an arc-sized result: the binary search's probes reach their slots
    inside the ``row_hits`` kernel. The arc mask is permuted once, outside
    the loop; the round makes one ``row_hits`` call (the bsearch step, in
    the scan) and one ``segment_sum`` (receivers)."""
    S = lambda shape, dtype=jnp.int32: _spec(one_chip, shape, dtype)  # noqa: E731
    blocks = e_pad // 2048
    ops = dispatch.GraphOperands(
        S((e_pad,)), S((e_pad,)), (S((blocks, 16, 128)), S((blocks, 16, 128)), S((blocks,))), ()
    )
    text = dispatch._fused_jit.lower(
        ops, S((n,)), S((arcs,), jnp.bool_), S((n,), jnp.bool_), S((n,)),
        n=n, n_iters=16, max_rounds=n + 1, R=1024, n_rows_pad=n,
    ).compile().as_text()
    gathers = _gathers(text)
    in_loop = [g for g in gathers if "/while/body/" in g[0]]
    assert any("/kcore.recv/" in name for name, _, _ in in_loop)
    # n + 1: the vertex vectors with the padding slots' sentinel entry
    assert all(size <= n + 1 for _, size, _ in in_loop), in_loop
    hindex = [g for g in in_loop if "/kcore.hindex/" in g[0]]
    assert all(out <= n + 1 for _, _, out in hindex), hindex
    # the one arc-sized gathered operand: the mask's permutation, before the loop
    assert [size for _, size, _ in gathers if size > n + 1] == [arcs + 1]
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln and " = " in ln]
    heads = sorted(c.strip().split(".", 1)[0] for c in calls)
    assert heads == ["%row_hits", "%segment_sum"], heads
    (row_hits,) = [c for c in calls if c.strip().startswith("%row_hits.")]
    assert "/while/body/" in row_hits and "/kcore.hindex/" in row_hits


def test_insertion_upper_bound_compiles_at_the_trickle_cell_shapes(one_chip, for_tpu):
    """The streaming engine's tight insertion upper bound — every +1 pass
    in one while_loop, with its pass and sweep counts — at the trickle
    cell's shapes: 65,536 vertices, 2^21 padded arc slots, 256 padded
    inserted edges. Each sweep of the propagation (``ub.prop``) and of the
    peel (``ub.peel``) gathers one vertex-sized table into one slot-sized
    result, and no gather reads a bool table."""
    from repro.streaming.engine import _ub_converge

    n, slots, ins = 1 << 16, 1 << 21, 256
    S = lambda shape, dtype=jnp.int32: _spec(one_chip, shape, dtype)  # noqa: E731
    compiled = _ub_converge.lower(
        S((n,)), S((n,)), S((slots,)), S((slots,)), S((slots,), jnp.bool_),
        S((ins,)), S((ins,)), S((ins,), jnp.bool_), n=n,
    ).compile()
    U, *counts = compiled.out_info
    assert U.shape == (n,) and len(counts) == 3
    assert all(c.shape == () and c.dtype == jnp.int32 for c in counts)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    text = compiled.as_text()
    gathers = _gathers(text)
    for scope in ("/while/body/while/body/ub.prop/", "/while/body/while/body/ub.peel/"):
        swept = [(size, out) for name, size, out in gathers if scope in name]
        assert swept == [(n, slots)], (scope, gathers)
    # the peel's 2U[src] is gathered once a pass, outside its loop
    assert sum(out == slots for _, _, out in gathers) == 3, gathers
    assert not re.search(rf"= pred\[{slots}\][^ ]* gather\(", text)
