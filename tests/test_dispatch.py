"""Kernel-dispatch layer (repro.core.dispatch): plan resolution, program
caching by operand shape, and — the load-bearing claim — BIT-equal cores
and per-round message bills between the Pallas-dispatched and the
XLA-segment-op supersteps across host-loop, fused, and streaming modes."""

import numpy as np
import pytest

from repro import platform
from repro.core import bz_core_numbers, dispatch as dmod
from repro.core.kcore import KCoreConfig, kcore_decompose
from repro.graph import generators as gen

# --------------------------- plan resolution --------------------------- #

def test_resolve_plan_explicit_modes():
    assert dmod.resolve_plan("xla").kind == "xla"
    assert dmod.resolve_plan("pallas").kind == "pallas"
    assert dmod.resolve_plan("on").kind == "pallas"
    assert dmod.resolve_plan("off").kind == "xla"


def test_resolve_plan_auto_is_xla_off_tpu(monkeypatch):
    """auto picks Pallas only where the kernels compile natively; on the
    CPU backend the tests run on it must be the XLA segment ops."""
    import jax

    assert jax.default_backend() == "cpu"
    monkeypatch.delenv(platform.ENV_DISPATCH, raising=False)
    platform.set_dispatch_mode(None)
    plan = dmod.resolve_plan("auto")
    assert plan.kind == "xla" and plan.interpret
    assert dmod.resolve_plan().kind == "xla"


def test_resolve_plan_env_and_override(monkeypatch):
    monkeypatch.setenv(platform.ENV_DISPATCH, "on")
    platform.set_dispatch_mode(None)
    assert dmod.resolve_plan().kind == "pallas"
    platform.set_dispatch_mode("off")
    try:
        assert dmod.resolve_plan().kind == "xla"
    finally:
        platform.set_dispatch_mode(None)


# --------------------------- program caching --------------------------- #

def _relabel(g, seed):
    """Same shapes (n, arcs, degree multiset), different arc contents."""
    from repro.graph.structs import Graph

    perm = np.random.default_rng(seed).permutation(g.n)
    return Graph.from_edges(np.stack([perm[g.src], perm[g.dst]], 1), n=g.n)


def _one_round_ref(graph, est):
    """One synchronous round in numpy, every vertex active: each vertex's
    h-index of min(est[v], est[u]) over its neighbours v by the sort
    identity (max_i min(desc[i], i + 1)), which vertices changed, and the
    receivers (a neighbour changed)."""
    h = np.zeros_like(est)
    for u in range(graph.n):
        nbrs = graph.dst[graph.offsets[u]:graph.offsets[u + 1]]
        vals = -np.sort(-np.minimum(est[nbrs], est[u]))
        h[u] = np.minimum(vals, np.arange(1, vals.size + 1)).max(initial=0)
    changed = h < est
    recv = np.zeros(graph.n, bool)
    recv[graph.src[changed[graph.dst]]] = True
    return h, changed, recv


def test_program_cache_hits_on_same_arcs():
    """Programs are cached by operand SHAPE, never by arc content: the same
    arcs, and other arcs at the same shapes, reuse one compiled program
    (the graph is a jit argument, not a constant baked into the program),
    while a new shape compiles anew — and every run stays exact."""
    import jax
    import jax.numpy as jnp

    from repro.core.jit_telemetry import compile_count
    from repro.core.kcore import _bs_iters

    plan = dmod.resolve_plan("pallas")
    g = gen.erdos_renyi(200, 600, seed=0)
    g2 = _relabel(g, 1)
    assert not np.array_equal(g.dst, g2.dst)
    it = _bs_iters(g.max_deg)

    def run(graph, n_iters):
        prog = dmod.masked_round_program(graph.n, n_iters, plan,
                                         graph.src, graph.dst)
        args = (jnp.asarray(graph.deg), jnp.ones(graph.num_arcs, bool),
                jnp.ones(graph.n, bool))
        out = jax.block_until_ready(prog(*args))
        for a, b in zip(out, _one_round_ref(graph, graph.deg)):
            np.testing.assert_array_equal(np.asarray(a), b)

    run(g, it)
    c0 = compile_count()
    run(g, it)        # same arcs
    run(g2, it)       # other arcs, same shapes
    assert compile_count() == c0
    g3 = gen.erdos_renyi(300, 900, seed=0)
    run(g3, _bs_iters(g3.max_deg))
    assert compile_count() > c0


# ------------------------ bit-equality parity -------------------------- #

_FAMILIES = [
    ("ba", lambda: gen.barabasi_albert(300, 3, seed=1)),
    ("er", lambda: gen.erdos_renyi(250, 700, seed=3)),
    ("star+isolated", lambda: gen.star(40)),
    # three 1024-row blocks, the first spilling into a second, padded
    # 2048-slot edge block: padding slots inside and between row blocks
    ("er-3-row-blocks", lambda: gen.erdos_renyi(2100, 3000, seed=6)),
]


def _assert_bit_equal(rx, rp):
    assert rx.dispatch == "xla" and rp.dispatch == "pallas"
    assert np.array_equal(rx.core, rp.core)
    assert rx.rounds == rp.rounds and rx.converged == rp.converged
    for f in ("messages_per_round", "active_per_round", "changed_per_round"):
        np.testing.assert_array_equal(getattr(rx.stats, f),
                                      getattr(rp.stats, f))


@pytest.mark.parametrize("name,make", _FAMILIES, ids=[f[0] for f in _FAMILIES])
@pytest.mark.parametrize("fused", [False, True], ids=["host-loop", "fused"])
def test_decompose_parity_pallas_vs_xla(name, make, fused):
    """kcore_decompose: forced Pallas dispatch (ELL h-index + blocked
    segment sum, interpret mode on CPU) is bit-equal to the XLA path and
    the BZ oracle, in both the host round loop and the fused while_loop.
    The Pallas route counts its masks in the blocked layout's slot order,
    whose padding slots (E_pad > E) must count nothing."""
    from repro.kernels.segment_sum.ops import blocked_layout

    g = make()
    assert blocked_layout(g.src, g.n).slot_edge.size > g.num_arcs
    rx = kcore_decompose(g, KCoreConfig(fused=fused, dispatch="xla"))
    rp = kcore_decompose(g, KCoreConfig(fused=fused, dispatch="pallas"))
    _assert_bit_equal(rx, rp)
    assert np.array_equal(rp.core, bz_core_numbers(g))


def _hub_graph():
    """A hub of degree 616 (600 leaves, and a 17-clique it belongs to) and
    13 isolated vertices: the hub's degree seed makes binary-search probes
    of 256 and above, which bf16 cannot hold exactly."""
    from repro.graph.structs import Graph

    clique = np.array([0, *range(601, 617)])
    iu = np.triu_indices(clique.size, k=1)
    edges = [[0, v] for v in range(1, 601)] + np.stack([clique[iu[0]], clique[iu[1]]], 1).tolist()
    return Graph.from_edges(np.asarray(edges), n=630)


def _hub_batches(eng, rng):
    """Delete ten hub arcs and a clique edge; put them back with ten more
    (twenty inserts at the hub take the degree seed); then random churn."""
    from repro.streaming import random_churn_batch
    from repro.streaming.delta import EdgeBatch

    spokes = [[0, v] for v in range(1, 11)]
    yield EdgeBatch.make(insert=np.asarray([[1, 2], [3, 4], [617, 618]]),
                         delete=np.asarray(spokes + [[601, 602]]))
    yield EdgeBatch.make(insert=np.asarray(spokes + [[0, v] for v in range(617, 627)]),
                         delete=np.asarray([[1, 2]]))
    yield random_churn_batch(eng.graph, 10, 10, rng)


def _random_batches(eng, rng):
    from repro.streaming import random_churn_batch

    for _ in range(3):
        yield random_churn_batch(eng.graph, 10, 10, rng)


_STREAMS = [
    ("ba", lambda: gen.barabasi_albert(200, 3, seed=2), _random_batches),
    ("hub-616", _hub_graph, _hub_batches),
]


@pytest.mark.parametrize("name,make,batches", _STREAMS, ids=[s[0] for s in _STREAMS])
@pytest.mark.parametrize("frontier", ["dense", "fused"])
def test_streaming_parity_pallas_vs_xla(frontier, name, make, batches):
    """Streaming engine (dense per-round AND fused batch re-convergence):
    REPRO_PALLAS routing gives the identical bill per churn batch. The
    padded slot arrays hold masked-off slots, which the Pallas route
    permutes into slot order and must not count. On the hub graph the
    binary search probes the hub at 256 and above, which the ``row_hits``
    kernel must move to the hub's slots exactly."""
    from repro.streaming import StreamingConfig, StreamingKCoreEngine

    def run(mode, frontier):
        platform.set_dispatch_mode(mode)
        try:
            g = make()
            eng = StreamingKCoreEngine(g, StreamingConfig(frontier=frontier))
            rng = np.random.default_rng(7)
            out = []
            for batch in batches(eng, rng):
                res = eng.apply_batch(batch)
                out.append((res.stats.messages_per_round.tolist(),
                            res.stats.active_per_round.tolist(),
                            res.stats.changed_per_round.tolist(),
                            res.seed_strategy,
                            eng.core.tolist()))
                assert not eng._padded_slots()[2].all()
            assert np.array_equal(eng.core, bz_core_numbers(eng.graph))
            return out
        finally:
            platform.set_dispatch_mode(None)

    xla = run("xla", frontier)
    assert xla == run("pallas", frontier)
    if name == "hub-616":
        # the hub (degree 626) re-converges from its degree seed: its first
        # probe is 313; the clique that lost an edge holds core 15
        assert xla[1][3] == "degree" and xla[1][4][0] == 15


@pytest.mark.parametrize("dtype", [np.bool_, np.float32])
@pytest.mark.parametrize("F", [1, 3])
def test_segment_sum_slot_order_matches_arc_order(dtype, F):
    """``segment_sum_arrays`` over values in the layout's slot order (zero
    on padding slots) equals ``segment_sum_blocked`` over the same values
    in arc order on the same layout: bit-equal counts for bool indicators,
    the same f32 sums for floats. ``to_slots`` makes that order, on the
    host and on the device alike."""
    import jax.numpy as jnp

    from repro.kernels.segment_sum.ops import (blocked_layout, segment_sum_arrays,
                                               segment_sum_blocked, slot_rows, to_slots)

    r = np.random.default_rng(11)
    n, E = 300, 1500
    seg = r.integers(0, n, E)             # unsorted: the layout sorts it
    vals = (r.integers(0, 2, (E, F)).astype(bool) if dtype is np.bool_
            else r.normal(size=(E, F)).astype(np.float32))
    lo = blocked_layout(seg, n, R=128, be=256)
    slot_edge = lo.slot_edge.reshape(-1)
    assert slot_edge.size > E
    vals_slot = np.concatenate([vals, np.zeros((1, F), vals.dtype)])[slot_edge]
    np.testing.assert_array_equal(to_slots(vals, lo.slot_edge, 0), vals_slot)
    np.testing.assert_array_equal(
        np.asarray(to_slots(jnp.asarray(vals), jnp.asarray(lo.slot_edge), 0)), vals_slot)
    real = slot_edge < E
    np.testing.assert_array_equal(slot_rows(lo)[real], seg[slot_edge[real]])
    assert (slot_rows(lo) < n).all()
    layout = (jnp.asarray(lo.slot_edge), jnp.asarray(lo.rows_local), jnp.asarray(lo.block_row))
    slot = segment_sum_arrays(jnp.asarray(vals_slot), *layout,
                              R=lo.R, n_rows_pad=lo.n_rows_pad, n_rows=n)
    arc = segment_sum_blocked(jnp.asarray(vals), lo, n)
    assert slot.dtype == arc.dtype and slot.shape == (n, F)
    np.testing.assert_array_equal(np.asarray(slot), np.asarray(arc))
    if dtype is np.bool_:
        ref = np.zeros((n, F), np.int64)
        np.add.at(ref, seg, vals.astype(np.int64))
        np.testing.assert_array_equal(np.asarray(slot), ref)


def test_fused_outcome_records_dispatch():
    g = gen.barabasi_albert(150, 3, seed=4)
    from repro.core.runtime import fused_converge_dense

    out = fused_converge_dense(
        g.deg, np.ones(g.n, bool), g.src, g.dst,
        np.ones(g.num_arcs, bool), g.deg,
        n=g.n, n_iters=8, max_rounds=g.n + 1, dispatch="pallas")
    assert out.dispatch == "pallas" and out.converged


def test_kernel_shapes_size_the_slot_order_segment_sum():
    """The benchmark sizes ``segsum_roofline`` from the operands the round
    hands ``segment_sum_arrays`` (``bench.work.KernelShapes``): traced
    Pallas-plan programs record one segment-sum shape per layout, a flat
    bool mask of the layout's E_pad slots into n segments."""
    import jax.numpy as jnp

    from bench.work import KernelShapes
    from repro.core.kcore import _bs_iters

    plan = dmod.resolve_plan("pallas")
    g = gen.erdos_renyi(333, 901, seed=9)   # shapes no other test traces
    n, it = g.n, _bs_iters(g.max_deg)
    fused = dmod.fused_convergence_program(n, it, n + 1, plan, g.src, g.dst)
    host = dmod.masked_round_program(n, it, plan, g.src, g.dst)
    est, amask, act = jnp.asarray(g.deg), jnp.ones(g.num_arcs, bool), jnp.ones(n, bool)
    shapes = KernelShapes()
    uninstall = shapes.install()
    try:
        fused.lower(est, amask, act, est)
        host.lower(est, amask, act)
    finally:
        uninstall()
    slot_edge = fused.operands.seg[0]
    assert slot_edge.size > g.num_arcs
    assert shapes.segsum == {slot_edge.shape[0]: {(slot_edge.size, 1, n)}}
