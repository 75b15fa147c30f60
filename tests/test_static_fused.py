"""Static fused runtime (ISSUE 5): ``kcore_decompose(..., fused=True)`` —
the paper's from-scratch decomposition as one device-resident while_loop
through the shared runtime (core/runtime.py) — must be EXACT-equal to the
host round loop in cores AND per-round accounting (messages / active /
changed per round, round count, convergence flag), on both dispatch plans,
with a max_rounds cap, and through the sharded variant."""

import numpy as np
import pytest

from repro.core import KCoreConfig, bz_core_numbers, kcore_decompose, \
    kcore_decompose_sharded
from repro.distribution.compat import make_mesh
from repro.graph import generators as gen
from repro.graph.structs import Graph


def assert_result_equal(ref, got):
    """Full KCoreResult accounting equality (not just the cores)."""
    assert (ref.core == got.core).all()
    assert (ref.stats.messages_per_round
            == got.stats.messages_per_round).all()
    assert (ref.stats.active_per_round == got.stats.active_per_round).all()
    assert (ref.stats.changed_per_round
            == got.stats.changed_per_round).all()
    assert ref.rounds == got.rounds
    assert ref.converged == got.converged


GRAPHS = {
    "ba": lambda: gen.barabasi_albert(250, 4, seed=7),
    "er": lambda: gen.erdos_renyi(180, 700, seed=3),
    "chain": lambda: gen.chain(120),
    "star": lambda: gen.star(30),
    "complete": lambda: gen.complete(12),
    "edgeless": lambda: Graph.from_edges(np.zeros((0, 2), np.int64), n=9),
}


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_static_fused_equals_host_loop(name):
    g = GRAPHS[name]()
    ref = kcore_decompose(g)
    fus = kcore_decompose(g, fused=True)
    assert_result_equal(ref, fus)
    assert (fus.core == bz_core_numbers(g)).all()


def test_static_fused_via_config_flag():
    g = gen.barabasi_albert(150, 3, seed=1)
    ref = kcore_decompose(g)
    fus = kcore_decompose(g, KCoreConfig(fused=True))
    assert_result_equal(ref, fus)
    # keyword overrides the config in both directions
    assert_result_equal(ref, kcore_decompose(g, KCoreConfig(fused=True),
                                             fused=False))


def test_static_fused_backend_configs_identical():
    """The host loop on either dispatch plan (the XLA segment sums, or the
    Pallas ELL h-index and blocked segment sum) matches the fused loop
    bit-exactly."""
    g = gen.barabasi_albert(150, 3, seed=2)
    fus = kcore_decompose(g, fused=True)
    for plan in ("xla", "pallas"):
        host = kcore_decompose(g, KCoreConfig(dispatch=plan))
        assert host.dispatch == plan
        assert_result_equal(host, fus)


def test_static_fused_rejects_block_gs():
    g = gen.cycle(10)
    with pytest.raises(ValueError, match="jacobi"):
        kcore_decompose(g, KCoreConfig(mode="block_gs"), fused=True)


def test_static_fused_respects_max_rounds_cap():
    """A tight cap must stop the while_loop exactly where the host loop
    stops — same partial estimate, same accounting, converged=False."""
    g = gen.chain(60)
    ref = kcore_decompose(g, KCoreConfig(max_rounds=3))
    fus = kcore_decompose(g, KCoreConfig(max_rounds=3), fused=True)
    assert not ref.converged
    assert_result_equal(ref, fus)


def test_static_fused_sharded_1dev_mesh():
    g = gen.barabasi_albert(200, 4, seed=5)
    mesh = make_mesh((1,), ("data",))
    ref = kcore_decompose(g)
    fus = kcore_decompose_sharded(g, mesh, ("data",), fused=True)
    assert_result_equal(ref, fus)
    assert (fus.core == bz_core_numbers(g)).all()


def test_static_fused_reports_recompile_telemetry():
    """Back-to-back identical fused runs must be all cache hits — the
    O(log)-compiles claim of BENCH_static.json, measured not asserted."""
    g = gen.barabasi_albert(130, 3, seed=11)
    first = kcore_decompose(g, fused=True)
    second = kcore_decompose(g, fused=True)
    assert first.recompiles >= 0
    assert second.recompiles == 0
    assert_result_equal(first, second)
