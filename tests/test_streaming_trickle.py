"""Trickle updates: batches of a few edges on a small Kronecker graph under
``frontier="auto"``, by the core-maintenance protocol (uniformly chosen
existing edges deleted, the previous batch's deletions inserted back).
Every batch takes the tight insertion upper bound; the bound is sound,
its pass and sweep counts are the host loop's, and its spans nest under
``seed``."""

import numpy as np
import pytest

from repro.core import bz_core_numbers
from repro.graph import generators as gen
from repro.obs import trace
from repro.streaming import EdgeBatch, StreamingConfig, StreamingKCoreEngine
from repro.streaming.engine import _insertion_upper_bound_arrays, _ub_pass

BATCHES = 6
DELETES = 3  # per batch: ~0.1% of the graph's edges


def _replay(seed):
    """One record per batch: the result, the graph and cores before and
    after it, and the batch's layer spans."""
    g = gen.rmat(9, 8, seed=seed)
    eng = StreamingKCoreEngine(g, StreamingConfig(frontier="auto"))
    rng = np.random.default_rng(seed + 100)
    pending = np.zeros((0, 2), np.int64)
    out = []
    for _ in range(BATCHES):
        before, core0 = eng.graph, eng.core.copy()
        half = before.src < before.dst
        edges = np.stack([before.src[half], before.dst[half]], axis=1)
        gone = edges[rng.choice(edges.shape[0], DELETES, replace=False)]
        trace.reset()
        res = eng.apply_batch(EdgeBatch.make(insert=pending, delete=gone))
        pending = gone
        spans = trace.recent_layers()
        out.append({"res": res, "core0": core0, "after": eng.graph, "spans": spans})
    trace.reset()
    return out


@pytest.fixture(scope="module", params=[1, 2])
def replay(request):
    return _replay(request.param)


def test_trickle_batches_are_exact_and_take_the_tight_seed(replay):
    assert replay[0]["res"].delta.inserted.shape[0] == 0  # deletes only
    for b in replay:
        res = b["res"]
        assert res.converged
        assert res.seed_strategy == "tight"
        assert res.mode in ("compact", "fused")
        assert (res.core == bz_core_numbers(b["after"])).all()
    assert all(b["res"].delta.inserted.shape[0] == DELETES for b in replay[1:])


def test_trickle_upper_bound_is_sound_and_counts_the_host_loop_passes(replay):
    for b in replay:
        g, ins = b["after"], b["res"].delta.inserted
        old = b["core0"].astype(np.int64)
        live = np.ones(g.num_arcs, bool)
        U, passes = _insertion_upper_bound_arrays(g.n, g.src, g.dst, live, g.deg, old, ins)
        assert (U >= bz_core_numbers(g)).all()
        calls, prop, peel, U_host = 0, 0, 0, old.astype(np.int32)
        if ins.size:
            k = ins.shape[0]
            raised = True
            while raised:
                U_host, raised, p, q = _ub_pass(
                    U_host, g.deg.astype(np.int32), g.src, g.dst, live,
                    ins[:, 0].astype(np.int32), ins[:, 1].astype(np.int32),
                    np.ones(k, bool), n=g.n)
                calls, prop, peel = calls + 1, prop + int(p), peel + int(q)
        assert passes == calls
        assert (np.asarray(U_host) == U).all()
        # the engine ran the same passes over its padded slot arrays, and
        # the span counts their sweeps: each pass sweeps each loop once
        # or more
        (ub,) = [s for s in b["spans"] if s.name == "upper-bound"]
        assert ub.attrs["passes"] == passes
        assert (ub.attrs["prop_sweeps"], ub.attrs["peel_sweeps"]) == (prop, peel)
        assert prop >= passes and peel >= passes
        assert passes >= 1 or not ins.size


def test_trickle_seed_spans_nest_and_fit_inside_seed(replay):
    for b in replay:
        (seed,) = [s for s in b["spans"] if s.name == "seed"]
        assert seed.parent.name == "batch"
        ub = [s for s in b["spans"] if s.name == "upper-bound"]
        fr = [s for s in b["spans"] if s.name == "frontier"]
        assert len(ub) == len(fr) == 1
        assert ub[0].parent is seed and fr[0].parent is seed
        assert ub[0].seconds + fr[0].seconds <= seed.seconds
        assert seed.attrs["strategy"] == "tight"
        assert seed.attrs["frontier"] > 0
        # the bound's operand copies are counted where they are made
        stages = [s for s in b["spans"] if s.name == "stage" and s.parent is ub[0]]
        if b["res"].delta.inserted.size:
            (st,) = stages
            assert st.attrs["h2d_bytes"] > 0
        else:
            assert not stages
