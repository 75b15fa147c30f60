"""The benchmark's own generator, relabelling, reference and churn stream,
held against each other and against the program at a small size."""

import numpy as np
import pytest

from bench import graphs, reference, traffic

SPEC = {"generator": "graph500", "scale": 10, "edgefactor": 4, "A": 0.57, "B": 0.19,
        "C": 0.19, "D": 0.05, "graph_seed": 3}


@pytest.fixture(scope="module")
def g():
    return graphs.generate(SPEC)


def test_generator_matches_the_programs_analogue_of_musae_git():
    """The quadrant draws are the program's R-MAT at the same seed; the
    configuration's graph is that graph with its labels permuted."""
    from repro.graph import generators

    ours = graphs.kronecker(16, 4, 0, 0.57, 0.19, 0.19)
    theirs = generators.snap_analogue("MGF", 1.0, seed=0)
    assert (ours.n, ours.m) == (65536, 247030)
    assert np.array_equal(ours.offsets, theirs.offsets)
    assert np.array_equal(ours.dst, theirs.dst)
    g = graphs.generate({**SPEC, "scale": 16, "graph_seed": 0})
    assert (g.n, g.m) == (ours.n, ours.m)
    assert np.array_equal(np.sort(g.deg), np.sort(ours.deg))
    assert not np.array_equal(g.deg, ours.deg)


def test_group_permutation_keeps_every_aligned_group():
    p = graphs.group_permutation(1024, np.random.default_rng(1))
    assert sorted(p.tolist()) == list(range(1024))
    assert np.array_equal(p // 128, np.arange(1024) // 128)
    assert (p != np.arange(1024)).any()


def test_relabelled_cores_equal_the_permuted_reference(g):
    p = graphs.group_permutation(g.n, np.random.default_rng(7))
    h = graphs.relabel(g, p)
    assert h.m == g.m
    assert np.array_equal(np.sort(h.deg[p]), np.sort(g.deg))
    assert np.array_equal(reference.bz_cores(h)[p], reference.bz_cores(g))
    assert np.array_equal(graphs.keys_of(graphs.csr_from_keys(g.n, graphs.keys_of(h))),
                          graphs.keys_of(h))


def test_programs_decomposition_of_a_relabelled_graph_is_the_reference(g):
    from bench.harness import program_graph
    from repro.core import kcore_decompose

    ref = reference.jacobi_bills(g)
    assert np.array_equal(ref["core"], reference.bz_cores(g))
    p = graphs.group_permutation(g.n, np.random.default_rng(9))
    res = kcore_decompose(program_graph(graphs.relabel(g, p)), fused=True)
    assert np.array_equal(np.asarray(res.core)[p], ref["core"])
    assert np.array_equal(res.stats.messages_per_round, ref["messages"])
    assert np.array_equal(res.stats.active_per_round, ref["active"])
    assert res.rounds == ref["rounds"]


@pytest.mark.parametrize("frac", [0.05, 0.2])
def test_churn_stream_mirrors_the_servers_edge_set(g, frac):
    from bench.harness import program_graph
    from repro.streaming import EdgeBatch
    from repro.streaming.delta import PatchableCSR

    stream = traffic.ChurnStream(g, frac, seed=2**31 + 11)
    csr = PatchableCSR(program_graph(g))
    original = graphs.keys_of(g)
    last = np.zeros((0, 2), np.int64)
    for _ in range(4):
        b = stream.next_batch()
        half = max(2, int(frac * g.m))
        assert b.delete.shape[0] == half - half // 2
        # the batch inserts back exactly what the one before it deleted
        assert np.array_equal(b.insert, last)
        last = b.delete
        assert np.isin(graphs.edge_keys(g.n, b.delete), original).all()
        assert b.keys_after.size == g.m - b.delete.shape[0]
        csr.apply_batch(EdgeBatch.make(insert=b.insert, delete=b.delete))
        pg = csr.to_graph()
        got = graphs.keys_of(graphs.CSR(n=g.n, offsets=pg.offsets, dst=pg.dst))
        assert np.array_equal(got, b.keys_after)


def test_same_seed_same_traffic_other_seed_same_sizes(g):
    def two(seed):
        s = traffic.ChurnStream(g, 0.02, seed=seed)
        return s.next_batch(), s.next_batch()

    a, b, c = two(5), two(5), two(6)
    for x, y, z in zip(a, b, c):
        assert np.array_equal(x.insert, y.insert) and np.array_equal(x.delete, y.delete)
        assert x.insert.shape == z.insert.shape and x.delete.shape == z.delete.shape
    assert not np.array_equal(a[0].delete, c[0].delete)
