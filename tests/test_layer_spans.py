"""Always-timed layer spans (``repro.obs.trace.layer``): the span trees the
engines build, the phase timings read from them, the host-to-device bytes
and compiles they count, their mirror in the ``jax.profiler`` trace, and
the named device scopes of the dispatched round."""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch
from repro.core.kcore import _bs_iters
from repro.core.runtime import fused_converge_dense
from repro.graph import generators as gen
from repro.launch import kcore_run
from repro.obs import trace
from repro.obs.trace import NULL_SPAN, Tracer
from repro.obs.validate import span_tree_coverage, validate_chrome_trace
from repro.streaming import KCoreServer, StreamingConfig
from repro.streaming.delta import EdgeBatch

SCOPES = ("kcore.gather", "kcore.hindex", "kcore.changed", "kcore.recv", "kcore.stats")


@pytest.fixture
def ring():
    """The process tracer's ring, emptied before and after the test."""
    trace.reset()
    yield trace
    trace.reset()


def _tree(root):
    """{span: [direct children]} of the finished layer spans under root."""
    spans = [s for s in trace.recent_layers() if _root_of(s) is root]
    kids = {id(s): [] for s in spans}
    for s in spans:
        if s.parent is not None:
            kids[id(s.parent)].append(s)
    return spans, kids


def _root_of(s):
    while s.parent is not None:
        s = s.parent
    return s


def _check_nesting(spans):
    for s in spans:
        if s.parent is not None:
            assert s.parent.t0_ns <= s.t0_ns and s.t1_ns <= s.parent.t1_ns
        if s.name == "stage":
            p = s.parent
            while p is not None:
                assert p.name != "stage", "stage spans nest"
                p = p.parent


def _only(spans, name):
    (s,) = [x for x in spans if x.name == name]
    return s


def _fused_coverage(spans):
    fc = _only(spans, "fused-converge")
    covered = sum(s.seconds for s in spans
                  if s.name in ("stage", "device-loop", "stats-reconstruct")
                  and _has_ancestor(s, fc))
    return covered / fc.seconds


def _has_ancestor(s, a):
    p = s.parent
    while p is not None:
        if p is a:
            return True
        p = p.parent
    return False


def _batches(g):
    """A batch that deletes one edge and inserts a new one, and its undo."""
    old = np.asarray([[int(g.src[0]), int(g.dst[0])]])
    keys = set(zip(g.src.tolist(), g.dst.tolist()))
    new = next([u, v] for u in range(g.n) for v in range(u + 1, g.n) if (u, v) not in keys)
    new = np.asarray([new])
    return EdgeBatch.make(insert=new, delete=old), EdgeBatch.make(insert=old, delete=new)


def test_server_update_builds_the_batch_layer_tree(ring):
    g = gen.erdos_renyi(3000, 12000, seed=3)
    srv = KCoreServer(g, StreamingConfig(frontier="fused"))
    there, back = _batches(g)
    srv.update(there)                           # compiles outside the check
    srv.update(back)
    trace.reset()
    res = srv.update(there)
    assert res.mode == "fused"
    (root,) = [s for s in trace.recent_layers() if s.parent is None]
    assert root.name == "batch"
    spans, kids = _tree(root)
    assert len(spans) == 1 + root.descendants
    _check_nesting(spans)
    assert [s.name for s in kids[id(root)]] == ["csr-patch", "seed", "converge",
                                               "host-reconstruct"]
    conv = _only(spans, "converge")
    fc = _only(spans, "fused-converge")
    assert fc.parent is conv
    assert [s.name for s in kids[id(fc)]] == ["device-converge", "stats-reconstruct"]
    dev = _only(spans, "device-converge")
    assert [s.name for s in kids[id(dev)]][-1] == "device-loop"
    assert {s.name for s in kids[id(dev)]} == {"stage", "device-loop"}
    assert _fused_coverage(spans) >= 0.95
    # the result's phase walls are the spans' durations
    assert res.patch_s == _only(spans, "csr-patch").seconds
    assert res.seed_s == _only(spans, "seed").seconds
    assert res.converge_s == conv.seconds
    assert res.reconstruct_s == _only(spans, "host-reconstruct").seconds


def test_fused_decompose_builds_the_root_layer_tree(ring):
    g = gen.erdos_renyi(3000, 12000, seed=3)
    args = kcore_run.parse_args(["--fused"])
    kcore_run.decompose(args, g)                # compiles outside the check
    trace.reset()
    res, _wall = kcore_run.decompose(args, g)
    (root,) = [s for s in trace.recent_layers() if s.parent is None]
    assert root.name == "kcore.decompose"
    spans, kids = _tree(root)
    _check_nesting(spans)
    assert [s.name for s in kids[id(root)]] == ["stage", "fused-converge"]
    assert _fused_coverage(spans) >= 0.95
    dev = _only(spans, "device-converge")
    loop = _only(spans, "device-loop")
    stages = [s for s in spans if s.name == "stage"]
    assert res.phase_s["device-converge"] == dev.seconds
    assert res.phase_s["host-reconstruct"] == _only(spans, "stats-reconstruct").seconds
    assert res.phase_s["device-loop"] == loop.seconds
    assert res.phase_s["stage"] == pytest.approx(sum(s.seconds for s in stages), abs=1e-12)
    assert loop.seconds <= res.phase_s["device-converge"]


def test_stage_counts_the_bytes_it_copies_to_the_device(ring):
    g = gen.erdos_renyi(200, 600, seed=5)
    n, A = g.n, g.num_arcs
    seed = g.deg.astype(np.int32)
    active, mask = np.ones(n, bool), np.ones(A, bool)
    kw = dict(n=n, n_iters=_bs_iters(g.max_deg), max_rounds=n + 1, dispatch="xla")
    fused_converge_dense(seed, active, g.src, g.dst, mask, g.deg, **kw)
    (st,) = [s for s in trace.recent_layers() if s.name == "stage"]
    # seed, deg (int32, n); src, dst (int32, A); mask (bool, A); active (bool, n)
    assert st.attrs["h2d_bytes"] == 4 * n * 2 + 4 * A * 2 + A + n
    # arrays already on the device cost nothing
    trace.reset()
    on_dev = [jnp.asarray(a) for a in (seed, g.src, g.dst, mask)]
    fused_converge_dense(on_dev[0], active, on_dev[1], on_dev[2], on_dev[3], g.deg, **kw)
    (st,) = [s for s in trace.recent_layers() if s.name == "stage"]
    assert st.attrs["h2d_bytes"] == n + 4 * n                     # active, deg
    # the graph operands of a dispatched program: the sum over their leaves
    trace.reset()
    ops, _layout = dispatch._stage(dispatch.DispatchPlan("pallas"), g.src, g.dst, n, None)
    (st,) = trace.recent_layers()
    leaves = jax.tree_util.tree_leaves(ops)
    assert len(leaves) == 5                     # src, dst and the blocked layout
    assert st.attrs["h2d_bytes"] == sum(x.size * x.dtype.itemsize for x in leaves)


def test_a_fresh_compile_lands_in_the_span_that_paid_for_it(ring):
    @jax.jit
    def _fresh(x):
        return x * 5 + 2

    with trace.layer("outer") as outer:
        with trace.layer("cold") as cold:
            _fresh(jnp.arange(7_927)).block_until_ready()   # fresh signature
        with trace.layer("warm") as warm:
            _fresh(jnp.arange(7_927)).block_until_ready()
    assert cold.compiles >= 1
    assert warm.compiles == 0
    assert outer.compiles == cold.compiles
    # a fused program of a shape not seen before compiles in device-loop
    g = gen.erdos_renyi(173, 500, seed=7)
    trace.reset()
    fused_converge_dense(g.deg, np.ones(g.n, bool), g.src, g.dst, np.ones(g.num_arcs, bool),
                         g.deg, n=g.n, n_iters=_bs_iters(g.max_deg), max_rounds=g.n + 1,
                         dispatch="xla")
    by_name = {s.name: s for s in trace.recent_layers()}
    assert by_name["device-loop"].compiles >= 1
    assert by_name["stage"].compiles == 0
    assert by_name["fused-converge"].compiles >= by_name["device-loop"].compiles


def test_layer_spans_are_in_the_profiler_host_plane(ring, tmp_path):
    from jax.profiler import ProfileData

    x = jnp.arange(64)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.layer("probe-outer"):
            with trace.layer("probe-inner"):
                (x + 1).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            names |= {e.name for line in plane.lines for e in line.events}
    assert {"probe-outer", "probe-inner"} <= names


def test_detail_spans_stay_null_while_layer_spans_are_timed(ring):
    assert trace.enabled() is False
    assert trace.span("kcore.round", round=0) is NULL_SPAN
    with trace.layer("stage", h2d_bytes=0) as st:
        st.add("h2d_bytes", 16)
    assert trace.recent_layers() == [st]
    assert st.seconds > 0 and st.attrs == {"h2d_bytes": 16}
    assert trace.events() == []                 # nothing exported while off


def test_layer_spans_export_like_detail_spans_when_enabled():
    t = Tracer()
    t.enable()
    with t.layer("batch", batch_id=3) as b:
        with t.span("detail"):
            with t.layer("seed"):
                pass
        b.set(rounds=2)
    doc = t.chrome_trace()
    validate_chrome_trace(doc)
    by_name = {e["name"]: e for e in doc["traceEvents"]}
    assert by_name["batch"]["args"] == {"batch_id": 3, "rounds": 2, "compiles": 0}
    (cov,) = span_tree_coverage(doc["traceEvents"], "detail")
    assert cov["children"] == ["seed"]
    # the layer nesting skips the detail span in between
    assert [s.name for s in t.recent_layers()] == ["seed", "batch"]
    assert t.recent_layers()[0].parent is b and b.children_s.keys() == {"seed"}


def test_the_layer_ring_is_bounded(monkeypatch):
    monkeypatch.setattr(trace, "LAYER_RING", 4)
    t = Tracer()
    for i in range(10):
        with t.layer("stage", i=i):
            pass
    kept = t.recent_layers()
    assert [s.attrs["i"] for s in kept] == [6, 7, 8, 9]


def test_the_dispatched_round_carries_its_named_scopes():
    g = gen.erdos_renyi(120, 400, seed=2)
    n = g.n
    prog = dispatch.fused_convergence_program(n, _bs_iters(g.max_deg), n + 1,
                                              dispatch.DispatchPlan("xla"), g.src, g.dst)
    text = prog.lower(jnp.asarray(g.deg), jnp.ones(g.num_arcs, bool),
                      jnp.ones(n, bool), jnp.asarray(g.deg)).compile().as_text()
    op_names = [ln for ln in text.splitlines() if "op_name=" in ln]
    for scope in SCOPES:
        assert any(f"/{scope}/" in ln for ln in op_names), scope
