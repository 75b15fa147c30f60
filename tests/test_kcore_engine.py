"""Correctness of the paper's core: distributed k-core vs the BZ oracle,
message accounting invariants, and the paper's own claims."""

import numpy as np
import pytest

from repro.core import (KCoreConfig, bz_core_numbers, kcore_decompose,
                        work_bound)
from repro.graph import generators as gen


def test_fig1_example():
    """The paper's Fig. 1 graph: cores (A,B,E,F)=3, (G,H)=2, (C,D)=1."""
    g, expect = gen.fig1_example()
    assert (bz_core_numbers(g) == expect).all()
    res = kcore_decompose(g)
    assert (res.core == expect).all()
    assert res.converged


@pytest.mark.parametrize("family,kw", [
    ("erdos_renyi", dict(n=300, m=1200)),
    ("barabasi_albert", dict(n=400, m_attach=3)),
    ("community", dict(n=300, n_blocks=5, deg_in=6, deg_out=1)),
    ("rmat", dict(scale=8, edge_factor=4)),
])
@pytest.mark.parametrize("seed", [0, 1])
def test_engine_matches_bz(family, kw, seed):
    g = getattr(gen, family)(**kw, seed=seed)
    res = kcore_decompose(g)
    assert res.converged
    assert (res.core == bz_core_numbers(g)).all()


@pytest.mark.parametrize("mode,dispatch,fused", [
    ("jacobi", "xla", False), ("jacobi", "pallas", False),
    ("jacobi", "xla", True), ("block_gs", "xla", False),
], ids=["jacobi/xla/host", "jacobi/pallas/host", "jacobi/xla/fused",
        "block_gs/xla/host"])
def test_all_backends_agree(mode, dispatch, fused):
    g = gen.barabasi_albert(300, 4, seed=3)
    res = kcore_decompose(g, KCoreConfig(mode=mode, dispatch=dispatch),
                          fused=fused)
    assert res.dispatch == dispatch
    assert (res.core == bz_core_numbers(g)).all(), (mode, dispatch, fused)


def test_structured_graphs():
    assert (kcore_decompose(gen.complete(12)).core == 11).all()
    assert (kcore_decompose(gen.cycle(20)).core == 2).all()
    assert (kcore_decompose(gen.star(15)).core == 1).all()


def test_chain_depth():
    """Paper §II.B: the chain graph is the worst case — Θ(n) rounds (the
    estimate wave propagates one hop per round from each end)."""
    n = 120
    res = kcore_decompose(gen.chain(n))
    assert (res.core == 1).all()
    assert res.rounds >= n // 2 - 2          # depth ~ n/2 (two ends)


def test_social_graphs_converge_in_few_rounds():
    """Paper §II.B: 'normally, it takes only several rounds, such as 1 to
    10, to converge' on real (social-like) graphs — allow some slack for
    synthetic analogues."""
    g = gen.snap_analogue("FC", scale=0.3, seed=0)
    res = kcore_decompose(g)
    assert res.rounds <= 40, res.rounds


def test_message_accounting_invariants():
    g = gen.barabasi_albert(500, 4, seed=1)
    res = kcore_decompose(g)
    st = res.stats
    # round 0 = degree broadcast of every vertex = 2m messages
    assert st.messages_per_round[0] == 2 * g.m
    # messages only come from changed vertices: bounded by 2m each round
    assert (st.messages_per_round <= 2 * g.m).all()
    # total messages within the paper's work bound W
    assert st.total_messages <= work_bound(g, res.core)
    # active counts monotone-ish: first round everyone is active
    assert st.active_per_round[0] == g.n


def test_block_gs_never_worse():
    """Beyond-paper mode: Gauss-Seidel sweeps use fresher estimates, so
    total messages can only drop (monotone operator)."""
    g = gen.barabasi_albert(400, 4, seed=5)
    jac = kcore_decompose(g)
    gs = kcore_decompose(g, KCoreConfig(mode="block_gs", n_blocks=8))
    assert (gs.core == jac.core).all()
    assert gs.stats.total_messages <= jac.stats.total_messages
    assert gs.rounds <= jac.rounds


def test_empty_and_tiny():
    from repro.graph.structs import Graph
    g = Graph.from_edges(np.zeros((0, 2)), n=0)
    res = kcore_decompose(g)
    assert res.rounds == 0 and res.converged
    g1 = Graph.from_edges([(0, 1)], n=2)
    assert (kcore_decompose(g1).core == np.array([1, 1])).all()
