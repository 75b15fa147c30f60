"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret mode on CPU; same entry points target real TPUs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed (see "
                    "requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.kernels.embedding_bag.ops import embedding_bag_fused
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.kcore_hindex.ops import hindex_rows
from repro.kernels.kcore_hindex.ref import hindex_rows_ref
from repro.kernels.segment_sum.ops import blocked_layout, segment_sum_blocked
from repro.kernels.segment_sum.ref import segment_sum_ref


# ------------------------- kcore_hindex ------------------------------ #

@pytest.mark.parametrize("rows,width", [(8, 8), (64, 32), (130, 17), (5, 600)])
def test_hindex_shapes(rows, width, rng):
    nbr = rng.integers(0, 50, (rows, width)).astype(np.int32)
    est = rng.integers(0, 50, rows).astype(np.int32)
    out = hindex_rows(jnp.asarray(nbr), jnp.asarray(est), n_iters=7)
    ref = hindex_rows_ref(jnp.asarray(nbr), jnp.asarray(est))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 40), st.integers(1, 40), st.integers(0, 1000))
def test_hindex_property(rows, width, seed):
    """Kernel (binary search) vs oracle (sort identity) — independent
    algorithms must agree exactly."""
    r = np.random.default_rng(seed)
    nbr = r.integers(0, 64, (rows, width)).astype(np.int32)
    est = r.integers(0, 64, rows).astype(np.int32)
    out = hindex_rows(jnp.asarray(nbr), jnp.asarray(est), n_iters=8)
    ref = hindex_rows_ref(jnp.asarray(nbr), jnp.asarray(est))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ----------------- ELL layout: Pallas vs ref vs segment ops ----------- #
#
# The dispatch layer (repro.core.dispatch) claims the Pallas ELL h-index
# route is bit-equal to the XLA segment-op binary search on any static
# fully-live adjacency whose degree-0 vertices carry estimate 0. These
# property tests check that claim on ragged degree-bucketed layouts —
# including empty (sentinel-padded) rows, empty buckets, and degrees
# landing exactly on the pow2 bucket-width boundary.

def _ell_round_all(g, est, n_iters, hindex_fn):
    """One full h-index round over every bucket of g's ELL layout."""
    from repro.graph.structs import build_ell

    ell = build_ell(g, widths=(2, 4, 8, 32))
    est_ext = np.concatenate([est, np.zeros(1, np.int32)]).astype(np.int32)
    new_ext = est_ext.copy()
    for b in ell.buckets:
        h = hindex_fn(jnp.asarray(est_ext[b.nbrs]),
                      jnp.asarray(est_ext[b.ids]), n_iters)
        new_ext[b.ids] = np.asarray(h, np.int32)
    return new_ext[: g.n]


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 48), st.integers(0, 120), st.integers(0, 1000))
def test_ell_hindex_pallas_vs_ref_vs_segment(n, e, seed):
    """Pallas ELL kernel == sort-identity oracle == XLA segment-op binary
    search, on random ragged graphs with arbitrary (deg-0-zeroed) ests."""
    from repro.core.dispatch import arc_hits, hindex_by_bsearch
    from repro.core.kcore import _bs_iters
    from repro.graph.structs import Graph

    r = np.random.default_rng(seed)
    edges = r.integers(0, n, (e, 2))
    g = Graph.from_edges(edges, n=n)
    hi = max(g.max_deg, 1) * 2 + 1
    est = r.integers(0, hi, n).astype(np.int32)
    est[g.deg == 0] = 0          # the ELL-route exactness precondition
    n_iters = _bs_iters(hi)

    got_pallas = _ell_round_all(
        g, est, n_iters,
        lambda nbr, eu, it: hindex_rows(nbr, eu, n_iters=it))
    got_ref = _ell_round_all(
        g, est, n_iters, lambda nbr, eu, it: hindex_rows_ref(nbr, eu, it))
    est_j = jnp.asarray(est)
    seg = np.asarray(hindex_by_bsearch(
        est_j, arc_hits(est_j[jnp.asarray(g.dst)], jnp.asarray(g.src), g.n),
        n_iters))
    np.testing.assert_array_equal(got_pallas, got_ref)
    np.testing.assert_array_equal(got_pallas, seg)


def test_ell_hindex_pow2_boundary_and_empty_rows():
    """Deterministic edge cases: a star whose hub degree sits exactly ON a
    pow2 bucket width (8), leaf count NOT a row_multiple multiple (so the
    leaf bucket carries sentinel-padded rows), plus isolated vertices."""
    from repro.core.dispatch import arc_hits, hindex_by_bsearch
    from repro.core.kcore import _bs_iters
    from repro.graph.structs import Graph, build_ell

    # hub 0 -- leaves 1..8 (deg 8 == bucket width), 9..11 isolated
    edges = [(0, i) for i in range(1, 9)]
    g = Graph.from_edges(edges, n=12)
    ell = build_ell(g, widths=(2, 4, 8, 32))
    assert any(b.width == 8 and b.rows_real == 1 for b in ell.buckets)
    assert any(b.ids.shape[0] > b.rows_real for b in ell.buckets)

    est = g.deg.astype(np.int32)
    n_iters = _bs_iters(g.max_deg)
    got = _ell_round_all(
        g, est, n_iters,
        lambda nbr, eu, it: hindex_rows(nbr, eu, n_iters=it))
    est_j = jnp.asarray(est)
    seg = np.asarray(hindex_by_bsearch(
        est_j, arc_hits(est_j[jnp.asarray(g.dst)], jnp.asarray(g.src), g.n),
        n_iters))
    np.testing.assert_array_equal(got, seg)
    assert (got[9:] == 0).all()          # isolated vertices stay 0


# ------------- one binary search, every hit counter ------------------ #
#
# ``dispatch.hindex_by_bsearch`` is the package's one binary-search
# h-index; its callers differ only in how they count a probe's hits. Each
# counter, fed to it, must give the sort-identity oracle's h-index.

def _ragged_graphs():
    """Random graphs with a hub, isolated vertices and rows spread over
    several 128-row blocks."""
    from repro.graph.structs import Graph

    r = np.random.default_rng(5)
    for n, e in ((40, 90), (300, 900), (260, 400)):
        hub = r.integers(1, n, n // 2)
        edges = np.concatenate([r.integers(0, n, (e, 2)),
                                np.stack([np.zeros_like(hub), hub], 1)])
        yield Graph.from_edges(edges, n=n + 5)


def _bsearch_with(counter, g, est, n_iters):
    """The h-index of every vertex of g from ``est`` by the one binary
    search, its hits counted the way ``counter`` names."""
    from repro.core.dispatch import arc_hits, hindex_by_bsearch
    from repro.core.kcore import make_sharded_superstep
    from repro.core.outofcore import row_offset_hits
    from repro.distribution.compat import make_mesh
    from repro.graph.partition import shard_graph
    from repro.kernels.segment_sum.ops import row_hits_arrays, to_slots

    n, est_dst = g.n, est[g.dst]
    if counter == "shard_local":
        # the masked shard_map superstep, everyone active, on a 1-device mesh
        sg = shard_graph(g, 1)
        step, _ = make_sharded_superstep(sg, make_mesh((1,), ("data",)),
                                         ("data",), n_iters, masked=True)
        est_p = np.zeros((1, sg.verts_per_shard), np.int32)
        est_p[0, :n] = est
        new, _, _, _ = step(jnp.asarray(est_p), jnp.asarray(sg.src),
                            jnp.asarray(sg.dst), jnp.asarray(sg.arc_mask),
                            jnp.asarray(sg.deg), jnp.ones_like(est_p, bool))
        return np.asarray(new)[0, :n]
    if counter == "segment_sum":
        # arcs in any order
        perm = np.random.default_rng(1).permutation(g.num_arcs)
        hits = arc_hits(jnp.asarray(est_dst[perm]), jnp.asarray(g.src[perm]), n)
    elif counter == "row_hits":
        lo = blocked_layout(g.src, n, R=128, be=128)
        slots = jnp.asarray(to_slots(est_dst, lo.slot_edge, 0))

        def hits(mid):
            return row_hits_arrays(slots, mid, jnp.asarray(lo.rows_local),
                                   jnp.asarray(lo.block_row), R=lo.R,
                                   n_rows_pad=lo.n_rows_pad, n_rows=n)
    else:
        hits = row_offset_hits(jnp.asarray(est_dst), jnp.asarray(g.src),
                               jnp.asarray(g.offsets))
    return np.asarray(hindex_by_bsearch(jnp.asarray(est), hits, n_iters))


@pytest.mark.parametrize("counter", ["segment_sum", "row_hits",
                                     "row_offsets", "shard_local"])
def test_every_hit_counter_gives_the_oracles_hindex(counter):
    """The XLA segment sum (arcs in any order), the ``row_hits`` kernel on
    a blocked layout (interpret mode), a cumsum differenced at the CSR row
    offsets (the out-of-core block), and the shard-local segment sum on a
    1-device mesh: each counter fed to ``hindex_by_bsearch`` gives every
    vertex the sort-identity h-index of min(est[v], est[u]) over its
    neighbours, estimates above and below the degrees alike."""
    from repro.core.kcore import _bs_iters

    r = np.random.default_rng(11)
    for g in _ragged_graphs():
        est = r.integers(0, 2 * g.max_deg + 2, g.n).astype(np.int32)
        nbr = np.zeros((g.n, g.max_deg), np.int32)   # sentinel slots hold 0
        for u in range(g.n):
            nbr[u, :g.deg[u]] = est[g.dst[g.offsets[u]:g.offsets[u + 1]]]
        want = np.asarray(hindex_rows_ref(jnp.asarray(nbr), jnp.asarray(est)))
        got = _bsearch_with(counter, g, est, _bs_iters(int(est.max())))
        np.testing.assert_array_equal(got, want)
        assert (want[g.deg == 0] == 0).all() and want.max() > 1


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 400), st.integers(1, 80), st.integers(0, 100))
def test_segment_sum_int32_bit_exact(E, n, seed):
    """Counting bool indicators gives int32 counts BIT-equal to
    jax.ops.segment_sum — the exactness the dispatched superstep's message
    accounting rests on (every k-core operand is a 0/1 indicator)."""
    r = np.random.default_rng(seed)
    seg = np.sort(r.integers(0, n, E))    # sorted-COO like arc sources
    vals = r.integers(0, 2, E).astype(bool)
    lo = blocked_layout(seg, n, R=128, be=128)
    out = segment_sum_blocked(jnp.asarray(vals), lo, n)[:, 0]
    assert out.dtype == jnp.int32
    ref = np.asarray(jax.ops.segment_sum(jnp.asarray(vals, jnp.int32),
                                         jnp.asarray(seg), num_segments=n))
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_segment_sum_refuses_inexact_inputs(monkeypatch):
    """Non-indicator integers have no exact path, and a segment that could
    count past the f32 accumulator's exact range is refused on the host."""
    from repro.kernels.segment_sum import ops

    seg = np.array([0, 0, 0, 1])
    lo = blocked_layout(seg, 2, R=128, be=128)
    assert lo.max_count == 3
    with pytest.raises(TypeError, match="bool indicators or floats"):
        segment_sum_blocked(jnp.ones(4, jnp.int32), lo, 2)
    monkeypatch.setattr(ops, "EXACT_COUNT_LIMIT", 3)
    with pytest.raises(ValueError, match="counts exactly only below 3"):
        blocked_layout(seg, 2, R=128, be=128)


# probes at the bf16 rounding edges and past f32's exact integers
_PROBES = np.array([0, 1, 255, 256, 257, 9_699, 65_536, (1 << 23) - 1, (1 << 24) + 1,
                    (1 << 31) - 1], np.int64)


def _hub_rows(n, hubs, E, r):
    """Arc sources (rows): ``hubs`` rows own most arcs, the rest uniform."""
    return np.concatenate([r.choice(hubs, E // 2), r.integers(0, n, E - E // 2)])


@pytest.mark.parametrize("name,n,E,R,be", [
    # five 128-slot edge blocks for one 128-row block
    ("several-edge-blocks-per-row-block", 300, 1500, 128, 128),
    # rows 128..383 have no arcs: two row blocks of padding slots only
    ("empty-row-blocks", 600, 700, 128, 128),
    # the round's defaults: a 2048-slot edge block spilling into a second
    ("default-tiles", 2100, 5000, 1024, 2048),
])
def test_row_hits_counts_each_rows_probe_hits(name, n, E, R, be):
    """``row_hits_arrays`` (interpret mode) against numpy over arcs:
    counts[r] = #{arcs of row r : est >= probe[r] > 0}. Estimates sit at,
    just below and just above each row's probe, so a probe that crosses the
    MXU rounded (257 as bf16 is 256) or in f32 (2^24 + 1) miscounts."""
    from repro.kernels.segment_sum.ops import row_hits_arrays, to_slots

    r = np.random.default_rng(len(name))
    if name == "empty-row-blocks":
        seg = np.concatenate([r.integers(0, 128, E // 2), r.integers(384, n, E - E // 2)])
    else:
        seg = _hub_rows(n, np.array([3, 5, 77]), E, r)
    probe = np.where(r.random(n) < 0.7, r.choice(_PROBES, n), r.integers(0, 1 << 31, n))
    est = probe[seg] + r.integers(-1, 2, E)
    est = np.where(r.random(E) < 0.1, r.integers(0, 1 << 31, E), est)
    probe, est = probe.astype(np.int32), np.clip(est, 0, (1 << 31) - 1).astype(np.int32)
    lo = blocked_layout(seg, n, R=R, be=be)
    assert lo.slot_edge.size > E                               # padding slots
    if name == "several-edge-blocks-per-row-block":
        assert np.bincount(lo.block_row).max() >= 3
    if name == "empty-row-blocks":
        assert np.isin([1, 2], lo.block_row).all()
        assert not np.isin(np.arange(128, 384), seg).any()
    out = row_hits_arrays(
        jnp.asarray(to_slots(est, lo.slot_edge, 0)), jnp.asarray(probe),
        jnp.asarray(lo.rows_local), jnp.asarray(lo.block_row),
        R=lo.R, n_rows_pad=lo.n_rows_pad, n_rows=n)
    hit = (est >= probe[seg]) & (probe[seg] > 0)
    ref = np.bincount(seg, weights=hit, minlength=n).astype(np.int64)
    assert out.dtype == jnp.int32 and out.shape == (n,)
    np.testing.assert_array_equal(np.asarray(out), ref)
    assert 0 < ref.sum() < E


# ------------------------- flash attention --------------------------- #

@pytest.mark.parametrize("B,Sq,Sk,Hq,Hkv,D", [
    (2, 128, 128, 4, 2, 32),
    (1, 256, 256, 8, 1, 64),     # MQA
    (2, 64, 64, 4, 4, 16),       # MHA
])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 32),
                                           (False, None)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, Sq, Sk, Hq, Hkv, D, causal, window, dtype):
    key = jax.random.key(42)
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, Sq, Hq, D), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 2), (B, Sk, Hkv, D), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 3), (B, Sk, Hkv, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window)
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    ref = attention_ref(qf, kf, vf, causal=causal, window=window) \
        .reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                 ref.astype(jnp.float32)))) < tol


# ------------------------- segment sum -------------------------------- #

@pytest.mark.parametrize("E,n,F", [(1000, 300, 8), (4096, 64, 16),
                                   (37, 10, 4), (513, 513, 1)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_segment_sum_sweep(E, n, F, dtype, rng):
    seg = rng.integers(0, n, E)
    vals = rng.normal(size=(E, F)).astype(dtype)
    lo = blocked_layout(seg, n, R=128, be=256)
    out = segment_sum_blocked(jnp.asarray(vals), lo, n)
    ref = segment_sum_ref(jnp.asarray(vals), jnp.asarray(seg), n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 500), st.integers(1, 100), st.integers(0, 100))
def test_segment_sum_property(E, n, seed):
    r = np.random.default_rng(seed)
    seg = r.integers(0, n, E)
    vals = r.normal(size=(E, 4)).astype(np.float32)
    lo = blocked_layout(seg, n, R=128, be=128)
    out = segment_sum_blocked(jnp.asarray(vals), lo, n)
    ref = segment_sum_ref(jnp.asarray(vals), jnp.asarray(seg), n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


# ------------------------- embedding bag ------------------------------ #

@pytest.mark.parametrize("V,D,B,L", [(100, 8, 4, 5), (500, 24, 13, 7),
                                     (1000, 32, 32, 20)])
def test_embedding_bag_sweep(V, D, B, L, rng):
    table = jax.random.normal(jax.random.key(0), (V, D))
    idx = rng.integers(-1, V, (B, L)).astype(np.int32)
    out = embedding_bag_fused(table, jnp.asarray(idx))
    ref = embedding_bag_ref(table, jnp.asarray(idx))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)
