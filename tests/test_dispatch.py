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


def test_program_cache_hits_on_same_arcs():
    """Programs are cached by operand SHAPE, never by arc content: the same
    arcs, and other arcs at the same shapes, reuse one compiled program
    (the graph is a jit argument, not a constant baked into the program),
    while a new shape compiles anew — and every run stays exact."""
    import jax
    import jax.numpy as jnp

    from repro.core.jit_telemetry import compile_count
    from repro.core.kcore import _bs_iters, masked_round_segment

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
        ref = masked_round_segment(args[0], jnp.asarray(graph.src),
                                   jnp.asarray(graph.dst), *args[1:],
                                   graph.n, n_iters)
        for a, b in zip(out, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

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
    the BZ oracle, in both the host round loop and the fused while_loop."""
    g = make()
    rx = kcore_decompose(g, KCoreConfig(fused=fused, dispatch="xla"))
    rp = kcore_decompose(g, KCoreConfig(fused=fused, dispatch="pallas"))
    _assert_bit_equal(rx, rp)
    assert np.array_equal(rp.core, bz_core_numbers(g))


def test_streaming_parity_pallas_vs_xla():
    """Streaming engine (dense per-round AND fused batch re-convergence):
    REPRO_PALLAS routing gives the identical bill per churn batch."""
    from repro.streaming import (StreamingConfig, StreamingKCoreEngine,
                                 random_churn_batch)

    def run(mode, frontier):
        platform.set_dispatch_mode(mode)
        try:
            g = gen.barabasi_albert(200, 3, seed=2)
            eng = StreamingKCoreEngine(g, StreamingConfig(frontier=frontier))
            rng = np.random.default_rng(7)
            out = []
            for _ in range(3):
                res = eng.apply_batch(random_churn_batch(eng.graph, 10, 10,
                                                         rng))
                out.append((res.stats.messages_per_round.tolist(),
                            res.stats.active_per_round.tolist(),
                            eng.core.tolist()))
            assert np.array_equal(eng.core, bz_core_numbers(eng.graph))
            return out
        finally:
            platform.set_dispatch_mode(None)

    for frontier in ("dense", "fused"):
        assert run("xla", frontier) == run("pallas", frontier), frontier


def test_fused_outcome_records_dispatch():
    g = gen.barabasi_albert(150, 3, seed=4)
    from repro.core.runtime import fused_converge_dense

    out = fused_converge_dense(
        g.deg, np.ones(g.n, bool), g.src, g.dst,
        np.ones(g.num_arcs, bool), g.deg,
        n=g.n, n_iters=8, max_rounds=g.n + 1, dispatch="pallas")
    assert out.dispatch == "pallas" and out.converged
