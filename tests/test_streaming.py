"""Streaming k-core maintenance: delta layer (rebuild + in-place CSR patch),
incremental engine vs the BZ oracle under random churn, frontier modes, and
the query server."""

import numpy as np
import pytest

from repro.core import bz_core_numbers, kcore_decompose
from repro.graph import generators as gen
from repro.graph.structs import Graph
from repro.streaming import (EdgeBatch, KCoreServer, PatchableCSR, Request,
                             StreamingConfig, StreamingKCoreEngine,
                             apply_batch, canonical_edges,
                             random_churn_batch, warm_start_seed)


# ---------------------------------------------------------------------- #
# Delta layer
# ---------------------------------------------------------------------- #

def test_delta_matches_rebuild_from_edge_set():
    rng = np.random.default_rng(0)
    g = gen.erdos_renyi(60, 150, seed=0)
    edges = {tuple(e) for e in canonical_edges(g).tolist()}
    for _ in range(10):
        batch = random_churn_batch(g, 8, 8, rng)
        res = apply_batch(g, batch)
        # reference: plain python set simulation, deletes then inserts
        for u, v in batch.delete.tolist():
            edges.discard((min(u, v), max(u, v)))
        for u, v in batch.insert.tolist():
            if u != v:
                edges.add((min(u, v), max(u, v)))
        ref = Graph.from_edges(np.asarray(sorted(edges), np.int64),
                               n=res.graph.n)
        assert res.graph.m == ref.m
        assert (res.graph.src == ref.src).all()
        assert (res.graph.dst == ref.dst).all()
        g = res.graph
        edges = {tuple(e) for e in canonical_edges(g).tolist()}


def test_delta_noops_and_cleanse():
    g = Graph.from_edges([(0, 1), (1, 2)], n=4)
    # insert existing edge, a self-loop, and a duplicate pair; delete a
    # non-existent edge and one referencing an unknown vertex
    res = apply_batch(g, EdgeBatch.make(
        insert=[(1, 0), (2, 2), (3, 0), (0, 3)],
        delete=[(0, 2), (7, 9)]))
    assert res.graph.m == 3
    assert res.inserted.shape[0] == 1           # only (0, 3) was new
    assert res.deleted.shape[0] == 0
    assert res.touched.tolist() == [0, 3]


def test_delta_grows_vertex_set():
    g = Graph.from_edges([(0, 1)], n=2)
    res = apply_batch(g, EdgeBatch.make(insert=[(1, 5)]))
    assert res.graph.n == 6
    assert res.graph.m == 2
    res.graph.validate()


# ---------------------------------------------------------------------- #
# In-place CSR patching
# ---------------------------------------------------------------------- #

def test_patched_csr_equals_rebuilt_csr_under_random_churn():
    """Property: after every random churn batch the in-place patched CSR
    materializes to the exact same Graph (src/dst/offsets/deg) as the
    rebuild path, reports the identical effective delta, and its raw slot
    arrays hold the same live arc multiset — across deletions creating
    holes, inserts filling them, vertex growth, no-op churn, and forced
    compactions (tight slack)."""
    rng = np.random.default_rng(2)
    g = gen.erdos_renyi(80, 220, seed=0)
    patcher = PatchableCSR(g, slack=0.15, min_slack=2, compact_dead_frac=0.2)
    for t in range(25):
        batch = random_churn_batch(g, 10, 10, rng)
        if t % 6 == 0:   # growth + duplicate + self-loop + unknown delete
            batch = EdgeBatch.make(
                insert=np.concatenate(
                    [batch.insert, [[g.n + 1, 0], [3, 3], [1, 2], [2, 1]]]),
                delete=np.concatenate([batch.delete, [[900, 901]]]))
        ref = apply_batch(g, batch)
        got = patcher.apply_batch(batch)
        assert (got.inserted == ref.inserted).all()
        assert (got.deleted == ref.deleted).all()
        assert (got.touched == ref.touched).all()
        # raw slot arrays: live arc multiset == the rebuilt arc set
        live_arcs = np.stack([patcher.src[patcher.live],
                              patcher.dst[patcher.live]], axis=1)
        order = np.lexsort((live_arcs[:, 1], live_arcs[:, 0]))
        assert (live_arcs[order, 0] == ref.graph.src).all()
        assert (live_arcs[order, 1] == ref.graph.dst).all()
        # materialized Graph: exact equality, valid CSR
        mat = patcher.to_graph()
        mat.validate()
        assert mat.n == ref.graph.n and mat.m == ref.graph.m
        assert (mat.src == ref.graph.src).all()
        assert (mat.dst == ref.graph.dst).all()
        assert (mat.offsets == ref.graph.offsets).all()
        assert (mat.deg == ref.graph.deg).all()
        g = ref.graph
    assert patcher.compactions > 0   # the tight slack must have forced some


def test_patched_csr_row_overflow_compacts():
    """Inserting many edges at one vertex overflows its slack row and must
    trigger a compaction, not corruption."""
    g = Graph.from_edges([(0, 1)], n=6)
    p = PatchableCSR(g, slack=0.0, min_slack=1)
    res = p.apply_batch(EdgeBatch.make(insert=[(0, 2), (0, 3), (0, 4),
                                               (0, 5)]))
    assert res.compacted
    assert p.m == 5
    assert (p.to_graph().deg == np.array([5, 1, 1, 1, 1, 1])).all()


# ---------------------------------------------------------------------- #
# Warm-start seeding
# ---------------------------------------------------------------------- #

def _bound_on_slots(graph, oce, inserted, rng):
    """The vectorized bound over the graph's arcs scattered among 5% dead
    slots whose ``src`` and ``dst`` are arbitrary vertices."""
    from repro.streaming.engine import _insertion_upper_bound_arrays
    m = graph.num_arcs
    slots = m + m // 20 + 3
    keep = np.sort(rng.choice(slots, m, replace=False))
    src = rng.integers(0, graph.n, slots).astype(np.int32)
    dst = rng.integers(0, graph.n, slots).astype(np.int32)
    live = np.zeros(slots, bool)
    src[keep], dst[keep], live[keep] = graph.src, graph.dst, True
    return _insertion_upper_bound_arrays(graph.n, src, dst, live, graph.deg,
                                         oce, inserted)[0]


@pytest.mark.parametrize("case", ["churn", "dead_slots", "ties", "at_cap"])
def test_vectorized_insertion_bound_matches_unionfind_reference(case):
    """The jitted segment-op insertion upper bound must equal the host-side
    union-find reference exactly (same passes, same peel fixpoints): from
    the old cores (``churn``), over slot arrays with dead slots, from one
    level shared by every vertex (``ties``), and with U equal to the
    degree cap at half the vertices (``at_cap``)."""
    from repro.streaming.engine import (_insertion_upper_bound,
                                        _insertion_upper_bound_unionfind)
    rng = np.random.default_rng(7)
    for g in (gen.erdos_renyi(100, 300, seed=2),
              gen.barabasi_albert(120, 3, seed=2)):
        core = bz_core_numbers(g).astype(np.int64)
        for _ in range(4):
            batch = random_churn_batch(g, 15, 10, rng)
            d = apply_batch(g, batch)
            oce = np.zeros(d.graph.n, np.int64)
            oce[: g.n] = core
            if case == "ties":
                oce[:] = int(np.median(core))
            elif case == "at_cap":
                half = rng.random(d.graph.n) < 0.5
                oce[half] = d.graph.deg[half]
            if case == "dead_slots":
                U_vec = _bound_on_slots(d.graph, oce, d.inserted, rng)
            else:
                U_vec = _insertion_upper_bound(d.graph, oce, d.inserted)
            U_ref = _insertion_upper_bound_unionfind(d.graph, oce,
                                                     d.inserted)
            assert (U_vec == U_ref).all()
            g, core = d.graph, bz_core_numbers(d.graph).astype(np.int64)


def test_seed_is_upper_bound_on_new_cores():
    """The locality theorem needs seed >= exact new cores pointwise; check
    on random churn over several families."""
    rng = np.random.default_rng(3)
    for g in (gen.erdos_renyi(120, 400, seed=1),
              gen.barabasi_albert(150, 3, seed=1),
              gen.rmat(7, 3, seed=1)):
        core = bz_core_numbers(g)
        for _ in range(5):
            batch = random_churn_batch(g, 12, 12, rng)
            delta = apply_batch(g, batch)
            seed, region = warm_start_seed(delta.graph, core, delta)
            new_core = bz_core_numbers(delta.graph)
            assert (seed >= new_core).all()
            # every vertex whose core increased must be in the region
            inc = new_core > np.pad(core, (0, delta.graph.n - g.n))
            assert (~inc | region).all()
            g, core = delta.graph, new_core


# ---------------------------------------------------------------------- #
# Incremental engine vs the BZ oracle
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("family,kw", [
    ("erdos_renyi", dict(n=250, m=1000)),
    ("barabasi_albert", dict(n=300, m_attach=3)),
    ("rmat", dict(scale=8, edge_factor=4)),
])
@pytest.mark.parametrize("seed", [0, 1])
def test_incremental_matches_bz_over_batches(family, kw, seed):
    """Property-style: after EVERY random insert/delete batch the incremental
    cores equal a from-scratch BZ recompute, and the incremental message
    bill never exceeds the from-scratch total."""
    g = getattr(gen, family)(**kw, seed=seed)
    eng = StreamingKCoreEngine(g)
    rng = np.random.default_rng(seed + 10)
    for _ in range(6):
        batch = random_churn_batch(eng.graph, 12, 12, rng)
        res = eng.apply_batch(batch)
        assert res.converged
        assert (res.core == bz_core_numbers(eng.graph)).all()
        scratch = kcore_decompose(eng.graph)
        assert res.total_messages <= scratch.stats.total_messages


def test_insertion_raises_core_without_incident_edge():
    """Path u-w-v plus inserted (u, v): w's core rises 1 -> 2 although no
    inserted edge touches w — the insertion region must reach it."""
    g = Graph.from_edges([(2, 0), (2, 1)], n=3)
    eng = StreamingKCoreEngine(g)
    res = eng.apply_batch(EdgeBatch.make(insert=[(0, 1)]))
    assert (res.core == np.array([2, 2, 2])).all()
    assert res.region_size >= 3


def test_batch_cascade_clique_from_empty():
    """Inserting all edges of K8 at once: every core jumps 0 -> 7, far more
    than +1 — exercises the multi-pass cascade in the region computation."""
    eng = StreamingKCoreEngine(Graph.from_edges(np.zeros((0, 2)), n=8))
    iu = np.triu_indices(8, k=1)
    res = eng.apply_batch(EdgeBatch.make(insert=np.stack(iu, axis=1)))
    assert (res.core == 7).all()


def test_delete_all_edges():
    g = gen.cycle(12)
    eng = StreamingKCoreEngine(g)
    res = eng.apply_batch(EdgeBatch.make(delete=canonical_edges(g)))
    assert (res.core == 0).all()
    assert eng.graph.m == 0


def test_empty_batch_is_free():
    eng = StreamingKCoreEngine(gen.barabasi_albert(100, 3, seed=0))
    res = eng.apply_batch(EdgeBatch.make())
    assert res.total_messages == 0
    assert res.rounds == 0
    assert (res.core == eng.init_result.core).all()


def test_compact_frontier_equals_dense():
    g = gen.barabasi_albert(200, 4, seed=9)
    dense = StreamingKCoreEngine(g, StreamingConfig(frontier="dense"))
    compact = StreamingKCoreEngine(g, StreamingConfig(frontier="compact"))
    rng = np.random.default_rng(4)
    for _ in range(4):
        batch = random_churn_batch(dense.graph, 10, 10, rng)
        r1, r2 = dense.apply_batch(batch), compact.apply_batch(batch)
        assert (r1.core == r2.core).all()
        assert (r1.stats.messages_per_round
                == r2.stats.messages_per_round).all()
        assert (r1.core == bz_core_numbers(dense.graph)).all()


# ---------------------------------------------------------------------- #
# Query server
# ---------------------------------------------------------------------- #

def test_server_queries_and_updates():
    g = gen.barabasi_albert(200, 3, seed=2)
    srv = KCoreServer(g)
    ref = bz_core_numbers(g)
    ids = np.array([0, 5, 17, 199])
    assert (srv.core_number(ids) == ref[ids]).all()
    assert srv.max_k() == int(ref.max())
    assert (srv.kcore_members(2) == np.flatnonzero(ref >= 2)).all()

    rng = np.random.default_rng(5)
    batch = random_churn_batch(g, 15, 15, rng)
    out = srv.serve([Request(op="update", batch=batch),
                     Request(op="core", vertices=ids),
                     Request(op="in_kcore", vertices=ids, k=2),
                     Request(op="max_k")])
    ref = bz_core_numbers(srv.engine.graph)
    assert (out[1].payload == ref[ids]).all()
    assert (out[2].payload == (ref[ids] >= 2)).all()
    assert out[3].payload == int(ref.max())
    st = srv.stats()
    assert st["updates_applied"] == 1 and st["queries_served"] == 3


def test_server_rejects_bad_ids():
    srv = KCoreServer(gen.cycle(10))
    # direct methods raise (library API) ...
    with pytest.raises(IndexError):
        srv.core_number([10])
    # ... but the request loop answers a structured error Response: a bad
    # request must never raise through a serving front end, and it must be
    # rejected before touching any state
    out = srv.serve([Request(op="nope"),
                     Request(op="core", vertices=[10]),
                     Request(op="in_kcore", vertices=[0]),       # missing k
                     Request(op="core", vertices=[0])])
    assert not out[0].ok and "unknown op" in out[0].error
    assert not out[1].ok and out[1].payload is None
    assert not out[2].ok and "requires k" in out[2].error
    assert out[3].ok and out[3].payload.tolist() == [2]
    assert srv.errors_returned == 3
    assert srv.stats()["queries_served"] == 1     # errors aren't queries
