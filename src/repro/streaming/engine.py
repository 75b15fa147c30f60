"""Warm-started incremental k-core engine.

Correctness rests on the locality theorem the static engine is built on
(core/kcore.py, paper §II.B): iterating est'(u) = H({min(est(v), est(u))})
converges to the exact core numbers from ANY per-vertex seed that upper
bounds them. So after a churn batch the engine only has to produce a sound
upper-bound seed — then frontier-localized supersteps re-converge exactly.

Seeding rules (all sound, proofs in the docstrings below):

  * a vertex whose core number cannot have increased keeps
    ``min(old_core, new_deg)`` — deletions only lower cores, and the old
    fixpoint is an upper bound of the new one outside the insertion region;
  * vertices that MAY have increased — the insertion region R — are re-seeded
    from a tight upper-bound vector computed by a batch generalization of
    the single-edge subcore theorem: +1 passes over level-set components
    anchored at inserted edges, pruned by a support peel
    (see ``_insertion_upper_bound``). The passes run as ONE jitted device
    program (``_ub_converge``), so seed cost is a single dispatch;
  * a per-batch COST MODEL (``repro.core.cost_model.choose_seed``) picks
    between the tight bound and a plain degree seed (sound by definition:
    deg >= core): estimated +1 passes x per-pass cost vs the extra fused
    rounds a degree seed costs. Bulk loads whose cores rise by many levels
    (a window filling from empty) seed from degrees; mid-churn batches
    whose cores barely move keep the low-message tight bound even when
    their insert fraction is large — the wall cliff of the old 25%-churn
    step function without giving up the message story.

The graph itself lives in a slack-padded in-place CSR (streaming/delta.py
``PatchableCSR``): a batch patches arc slots instead of rebuilding the
sorted COO, and the slot arrays feed the supersteps directly (dead slots
are masked arcs).

Message accounting mirrors core/messages.py: round 0 of a batch charges
deg(u) for every vertex whose seed differs from its previously broadcast
value (it must re-announce), plus 2 messages per inserted/deleted edge (the
link handshake/teardown); every later round charges deg(u) per vertex whose
estimate decreased. This makes "messages per batch" directly comparable to
the from-scratch total the paper reports.

Four frontier execution modes (plus ``auto``, which picks per batch):

  * ``dense``   — full-width jitted masked superstep
    (core.dispatch.masked_round_program): one program for the whole
    stream, frontier as a boolean mask;
  * ``compact`` — per-round extraction of the active subgraph, padded to
    powers of two so jit recompiles only O(log n) distinct shapes; work per
    round is proportional to the frontier, not the graph;
  * ``sharded`` — the masked superstep runs as a shard_map over a device
    mesh (core.make_sharded_superstep(..., masked=True)): vertex state
    sharded by contiguous range, one est all_gather plus one 1-bit changed
    all_gather per round. The in-place CSR's slot arrays are already
    src-sorted, so sharding a churned graph needs no sort.
  * ``fused``   — the ENTIRE batch re-convergence runs as one device-resident
    ``lax.while_loop`` (core.dispatch.fused_convergence_program): no
    per-round host round-trips; the host gets back only the final
    estimate plus per-round stat buffers from which exact MessageStats
    are reconstructed. With a mesh attached the while_loop nests the
    masked shard_map superstep (``fused_sharded``). All fused-program shapes are high-water-marked
    (CSR capacity, shard arc blocks, h-index search depth) so a whole
    windowed replay compiles O(log) distinct jit signatures — measured,
    not asserted, via repro.core.jit_telemetry (``BatchResult.recompiles``).

All modes produce identical estimates and identical message counts.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import dispatch as _dispatch
from repro.core.cost_model import SeedCostModel, choose_seed
from repro.core.jit_telemetry import compile_count, compile_seconds
from repro.core.kcore import (KCoreConfig, _bs_iters, _receivers_arrays,
                              kcore_decompose, kcore_decompose_sharded,
                              make_sharded_superstep)
from repro.core.messages import MessageStats
from repro.core.runtime import fused_converge_dense, fused_converge_sharded
from repro.graph.padding import next_pow2 as _next_pow2
from repro.graph.padding import round_up as _round_up
from repro.graph.structs import Graph
from repro.obs import flight as _flight
from repro.obs import trace as _trace
from repro.streaming.delta import ChurnDelta, DeltaResult, EdgeBatch, \
    PatchableCSR

FRONTIER_MODES = ("dense", "compact", "sharded", "fused", "auto")


# ---------------------------------------------------------------------- #
# Config / result
# ---------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class StreamingConfig:
    frontier: str = "dense"          # one of FRONTIER_MODES
    max_rounds: int | None = None    # None -> n + 1 per batch (worst case)
    # "auto" picks compact below this initial-frontier fraction, else
    # fused (the sharded-fused variant when a mesh is attached)
    compact_threshold: float = 0.02
    # in-place CSR knobs (see delta.PatchableCSR)
    slack: float = 0.3
    min_slack: int = 4
    compact_dead_frac: float = 0.25
    # pre-seeds the padded live-arc shape (engine._padded_slots) so a
    # stream that grows into a known load doesn't walk its jitted programs
    # through every pow2 size on the way up (the windowed engine sets it
    # from the expected window size); 0 = grow organically
    min_arc_capacity: int = 0
    # per-batch seeding policy (repro.core.cost_model.choose_seed): the
    # tight subcore upper bound costs one +1 device pass per unit of core
    # raise — unbounded for bulk loads (a filling window raises cores by
    # tens) — while a plain degree seed (always sound: deg >= core) costs
    # extra fused re-convergence rounds instead. The model compares the
    # two in units of fused rounds and picks per batch; for small churn
    # (the streaming benchmark's 0.2-2%) the tight bound always wins, so
    # the incremental message story is unchanged. All frontier modes share
    # the seed, so cross-mode bill equality is unaffected either way.
    seed_model: SeedCostModel = SeedCostModel()


@dataclasses.dataclass
class BatchResult:
    """Outcome of one incremental batch."""

    core: np.ndarray          # exact core numbers after the batch
    rounds: int               # supersteps to re-converge (excl. seed round)
    converged: bool
    stats: MessageStats       # per-round accounting; [0] = seed broadcast
    delta: ChurnDelta         # what the batch actually changed
    region_size: int          # |R| — insertion region that was re-seeded up
    seed_changed: int         # vertices that had to rebroadcast at seed time
    mode: str = "dense"       # execution mode this batch actually ran in
    # per-phase walls: the durations of the batch's always-timed layer
    # spans (repro.obs.trace.layer), so a benchmark row gets the
    # patch/seed/converge/reconstruct breakdown without tracing enabled
    patch_s: float = 0.0      # host seconds spent patching the CSR in place
    seed_s: float = 0.0       # warm-start seed + initial frontier
    converge_s: float = 0.0   # re-convergence (device dispatch + rounds)
    reconstruct_s: float = 0.0  # host-side stats assembly
    # warm-start seeding decision (repro.core.cost_model.choose_seed):
    # "tight" = subcore upper bound, "degree" = plain degree seed, and the
    # pass-count estimate the cost model based the choice on
    seed_strategy: str = "tight"
    seed_est_passes: int = 0
    # fresh XLA compilations this batch caused (process-wide; 0 = every
    # jitted program was a cache hit — the shape-stability signal), and the
    # wall XLA spent on them (jit_telemetry.compile_seconds delta)
    recompiles: int = 0
    compile_s: float = 0.0
    # (whether the batch forced an O(m) CSR compaction: delta.compacted)
    # PatchableCSR health after the batch — long churn streams live or die
    # by compaction behavior, so it is first-class, not property-test-only:
    csr_compactions: int = 0  # cumulative O(m) compactions so far
    csr_dead_frac: float = 0.0   # hole slots / capacity (fragmentation)
    csr_occupancy: float = 0.0   # live arc slots / capacity (slack usage)

    @property
    def total_messages(self) -> int:
        return self.stats.total_messages


# ---------------------------------------------------------------------- #
# Warm-start seeding
# ---------------------------------------------------------------------- #

def _ub_pass_body(U, cap, src, dst, live, ins_u, ins_v, ins_live, n):
    """One vectorized +1 pass of the insertion upper bound (see below).

    All device-side segment ops; dead/padding arc slots carry live=False.
    Returns (U', raised_any, prop_sweeps, peel_sweeps), the last two the
    sweeps of the pass's two inner loops.

      1. bottleneck propagation: T(x) = max over paths from x to an
         inserted-edge endpoint of min(k_e, min U over the path) — the
         fixpoint of T(x) = max(A(x), max_{y~x} min(U(y), T(y))) where A is
         the best incident inserted-edge level. T(x) >= U(x) iff x's
         component in the level set G_{>=U(x)} contains a qualifying
         insertion (the union-find condition, as a max-min path problem);
      2. candidates: T(x) >= U(x) and deg(x) > U(x);
      3. synchronous support peel to the greatest fixpoint: survivors keep
         > U(x) neighbors that are themselves survivors at the same level
         or sit strictly above it. (Peeling order never changes the
         greatest fixpoint, so the parallel peel equals the sequential
         stack peel of the reference implementation.)

    Each sweep (scopes ``ub.prop``, ``ub.peel``) gathers one vertex-sized
    int32 table over the arc slots: the propagation min(U, T)[dst], the
    peel K[dst] with K = 2U + c, against lim = 2U[src] made once a pass
    (INT32_MAX on dead slots, which so never qualify). That is exact:
    K(y) > 2U(x) iff U(y) > U(x), or U(y) == U(x) and c(y). U never
    exceeds the largest degree (< 2^30), so 2U + 1 fits in int32.
    """
    k_ins = jnp.where(ins_live, jnp.minimum(U[ins_u], U[ins_v]),
                      jnp.int32(-1))
    A = jnp.full(n, -1, jnp.int32).at[ins_u].max(k_ins).at[ins_v].max(k_ins)

    def prop_body(state):
        T, _, sweeps = state
        with jax.named_scope("ub.prop"):
            val = jnp.where(live, jnp.minimum(U, T)[dst], jnp.int32(-1))
            T2 = jnp.maximum(T, jax.ops.segment_max(val, src, num_segments=n))
        return T2, (T2 > T).any(), sweeps + 1

    T, _, prop_sweeps = lax.while_loop(
        lambda s: s[1], prop_body, (A, jnp.bool_(True), jnp.int32(0)))

    cand0 = (T >= U) & (cap > U)
    lim = jnp.where(live, 2 * U[src], jnp.iinfo(jnp.int32).max)

    def peel_body(state):
        c, _, sweeps = state
        with jax.named_scope("ub.peel"):
            qual = (2 * U + c.astype(jnp.int32))[dst] > lim
            s = jax.ops.segment_sum(qual.astype(jnp.int32), src,
                                    num_segments=n)
        c2 = c & (s > U)
        return c2, (c2 != c).any(), sweeps + 1

    cand, _, peel_sweeps = lax.while_loop(
        lambda s: s[1], peel_body, (cand0, jnp.bool_(True), jnp.int32(0)))
    return jnp.where(cand, U + 1, U), cand.any(), prop_sweeps, peel_sweeps


@functools.partial(jax.jit, static_argnames=("n",))
def _ub_pass(U, cap, src, dst, live, ins_u, ins_v, ins_live, n):
    """One jitted +1 pass (kept as the single-pass entry point; the engine
    hot path runs ``_ub_converge`` instead)."""
    return _ub_pass_body(U, cap, src, dst, live, ins_u, ins_v, ins_live, n)


@functools.partial(jax.jit, static_argnames=("n",))
def _ub_converge(U, cap, src, dst, live, ins_u, ins_v, ins_live, n):
    """ALL +1 passes of the insertion upper bound in one device program.

    The pass loop used to live on host — one jitted ``_ub_pass`` dispatch
    plus a blocking ``raised`` sync per pass, ~20 passes per heavy batch.
    Fusing it into an outer ``lax.while_loop`` makes the whole seed
    computation a single dispatch with no host round-trips; each pass is
    the identical ``_ub_pass_body``, so the resulting U is unchanged
    (property-tested against the union-find reference).

    Returns (U, passes, prop_sweeps, peel_sweeps): ``passes`` counts every
    pass run, the last one (which raises nothing) included — as many as
    the host loop of ``_ub_pass`` calls would make — and the sweeps are
    summed over them."""
    def pass_body(state):
        U, _, passes, prop, peel = state
        U, raised, p, q = _ub_pass_body(U, cap, src, dst, live, ins_u, ins_v,
                                        ins_live, n)
        return U, raised, passes + 1, prop + p, peel + q

    zero = jnp.int32(0)
    U, _, passes, prop, peel = lax.while_loop(
        lambda s: s[1], pass_body, (U, jnp.bool_(True), zero, zero, zero))
    return U, passes, prop, peel


def _insertion_upper_bound_arrays(n: int, src, dst, live, deg,
                                  old_core_ext: np.ndarray,
                                  inserted: np.ndarray
                                  ) -> tuple[np.ndarray, int]:
    """Vectorized insertion upper bound over raw (masked) arc arrays.

    ``src``/``dst``/``live`` may be numpy or already-device arrays (the
    engine passes its padded CSR slot arrays); shapes should be stable
    across batches (pow2-padded) so the jitted pass compiles O(log) times.
    Returns (U, passes), ``passes`` as ``_ub_converge`` counts them (0
    when nothing was inserted). Runs as an ``upper-bound`` layer span
    (attributes ``passes``, ``prop_sweeps``, ``peel_sweeps``) whose
    operand copies are a ``stage`` span counting ``h2d_bytes``.
    """
    with _trace.layer("upper-bound", passes=0, prop_sweeps=0,
                      peel_sweeps=0) as ub:
        U = old_core_ext.astype(np.int64).copy()
        if inserted.size == 0 or n == 0:
            return U, 0
        ins_pad = _next_pow2(max(inserted.shape[0], 1))
        ins_u = np.zeros(ins_pad, np.int32)
        ins_v = np.zeros(ins_pad, np.int32)
        ins_live = np.zeros(ins_pad, bool)
        ins_u[: inserted.shape[0]] = inserted[:, 0]
        ins_v[: inserted.shape[0]] = inserted[:, 1]
        ins_live[: inserted.shape[0]] = True

        with _trace.layer("stage", h2d_bytes=0) as st:
            args = [_dispatch.to_device(st, a, dt) for a, dt in (
                (U, jnp.int32), (deg, jnp.int32), (src, None), (dst, None),
                (live, None), (ins_u, None), (ins_v, None), (ins_live, None))]
        U_np, passes, prop, peel = jax.device_get(_ub_converge(*args, n=n))
        ub.set(passes=int(passes), prop_sweeps=int(prop),
               peel_sweeps=int(peel))
        return U_np.astype(np.int64), int(passes)


def _insertion_upper_bound(new_g: Graph, old_core_ext: np.ndarray,
                           inserted: np.ndarray) -> np.ndarray:
    """Pointwise upper bound U >= new core numbers, tight around insertions.

    Batch generalization of the classic single-edge subcore theorem
    (Sariyuce et al., "Streaming algorithms for k-core decomposition"):
    inserting ONE edge (u, v) into a graph with exact cores c raises core
    numbers by at most 1, and only for vertices x with c(x) = k =
    min(c(u), c(v)) reachable from an endpoint through vertices of core k.

    We iterate +1 "passes" over an evolving bound vector U (initialized to
    the pre-batch exact cores, so U >= cores holds at the start):

      pass: a vertex x is RAISED by 1 iff
        (a) its component in the level set G_{>=U(x)} = {y : U(y) >= U(x)}
            (computed in the post-batch graph) contains an endpoint of an
            inserted edge e with min(U(u_e), U(v_e)) >= U(x); and
        (b) new_deg(x) > U(x) (a core number never exceeds the degree); and
        (c) x survives a support peel: iteratively discard candidates with
            fewer than U(x)+1 neighbors that are either candidates at the
            same level or have U > U(x) (a vertex cannot sit in a
            (U(x)+1)-core without U(x)+1 qualified neighbors).

    Passes repeat until no vertex is raised. Soundness (U_final >= new
    cores): induct over a sequential replay — deletions first (cores only
    drop, so U_0 = old cores stays an upper bound), then insertions one at
    a time. If the i-th insertion truly raises x from c_i(x) and
    U(x) = c_i(x) still, then the true subcore path (core values exactly
    c_i(x)) is a path in the level set G_{>=U(x)} because U >= c_i
    pointwise, the raising edge has min-endpoint-bound >= c_i(x), x's true
    (c_i(x)+1)-core membership forces >= U(x)+1 qualified neighbors (each
    with final core > U(x), hence eventually U > U(x) or a same-level
    candidate), and its degree exceeds U(x) — so a later pass raises x.
    The level-set connectivity is evaluated in the final graph, a supergraph
    of every intermediate one, which only enlarges components (safe: over-
    approximating raises costs extra seed broadcasts, never correctness).

    The passes run as ONE jitted device program (``_ub_converge``: an
    outer while_loop over ``_ub_pass_body`` — a max-min bottleneck
    propagation replaces the host-side union-find sweep; a synchronous
    segment-sum peel replaces the stack peel — both reach the same
    fixpoints, checked against ``_insertion_upper_bound_unionfind`` in the
    tests). The number of passes is bounded by the largest true core
    increase (1-2 for realistic churn; up to tens when a sliding window
    first fills).
    """
    return _insertion_upper_bound_arrays(
        new_g.n, new_g.src, new_g.dst, np.ones(new_g.num_arcs, bool),
        new_g.deg, old_core_ext, inserted)[0]


def _insertion_upper_bound_unionfind(new_g: Graph, old_core_ext: np.ndarray,
                                     inserted: np.ndarray) -> np.ndarray:
    """Host-side union-find reference for ``_insertion_upper_bound``.

    One arc sort + union-find sweep over levels per pass, O(m alpha) plus a
    stack peel, all numpy/Python. Kept as the oracle the vectorized path is
    property-tested against (tests/test_streaming.py).
    """
    n = new_g.n
    U = old_core_ext.astype(np.int64).copy()
    if inserted.size == 0 or n == 0:
        return U
    cap = new_g.deg.astype(np.int64)
    src, dst, offsets = new_g.src, new_g.dst, new_g.offsets
    half = src < dst
    e_u = src[half].astype(np.int64)
    e_v = dst[half].astype(np.int64)
    ins_u, ins_v = inserted[:, 0], inserted[:, 1]

    parent = np.zeros(n, np.int64)

    def find(x: int) -> int:
        root = x
        while parent[root] != root:
            root = parent[root]
        while parent[x] != root:        # path compression
            parent[x], x = root, parent[x]
        return int(root)

    while True:
        # --- per-pass structures on the current bound vector U ---------- #
        k_ins = np.minimum(U[ins_u], U[ins_v])
        A = np.full(n, -1, np.int64)    # best inserted-edge level per vertex
        np.maximum.at(A, ins_u, k_ins)
        np.maximum.at(A, ins_v, k_ins)
        lev_arc = np.minimum(U[e_u], U[e_v])
        arc_order = np.argsort(-lev_arc, kind="stable")
        vert_order = np.argsort(-U, kind="stable")

        parent[:] = np.arange(n)
        M = A.copy()                    # per-root max inserted-edge level
        marked = np.zeros(n, bool)

        ai, vi = 0, 0
        n_arcs = arc_order.shape[0]
        while vi < n:
            L = int(U[vert_order[vi]])
            # activate all arcs of the level set G_{>=L}
            while ai < n_arcs and lev_arc[arc_order[ai]] >= L:
                a = arc_order[ai]
                ra, rb = find(int(e_u[a])), find(int(e_v[a]))
                if ra != rb:
                    parent[ra] = rb
                    M[rb] = max(M[rb], M[ra])
                ai += 1
            # candidates at level L: connected to a qualifying insertion
            cand = []
            while vi < n and U[vert_order[vi]] == L:
                x = int(vert_order[vi])
                vi += 1
                if cap[x] > L and M[find(x)] >= L:
                    cand.append(x)
            if not cand:
                continue
            # support peel: survivors need >= L+1 neighbors with U > L or
            # surviving candidates at this level
            in_c = np.zeros(n, bool)
            in_c[cand] = True
            s = {x: int(np.count_nonzero(
                    (U[dst[offsets[x]:offsets[x + 1]]] > L)
                    | in_c[dst[offsets[x]:offsets[x + 1]]]))
                 for x in cand}
            stack = [x for x in cand if s[x] <= L]
            while stack:
                x = stack.pop()
                if not in_c[x]:
                    continue
                in_c[x] = False
                for y in dst[offsets[x]:offsets[x + 1]]:
                    y = int(y)
                    if in_c[y]:
                        s[y] -= 1
                        if s[y] == L:
                            stack.append(y)
            marked |= in_c
        if not marked.any():
            return U
        U[marked] += 1


def warm_start_seed(new_g: Graph, old_core: np.ndarray,
                    delta: ChurnDelta | DeltaResult
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Sound upper-bound seed for the new graph's core numbers.

    Returns (seed, region): seed (n,) int32 with seed >= new core pointwise;
    region (n,) bool marks the insertion region that was re-seeded upward.
    Outside the region the seed is min(old_core, new_deg) — deletions only
    lower cores, so the previous fixpoint stays an upper bound there.
    """
    n = new_g.n
    old_core_ext = np.zeros(n, np.int64)
    old_core_ext[: old_core.shape[0]] = old_core  # new vertices: old core 0
    new_deg = new_g.deg.astype(np.int64)

    U = _insertion_upper_bound(new_g, old_core_ext, delta.inserted)
    seed = np.minimum(U, new_deg)
    region = U > old_core_ext
    return seed.astype(np.int32), region


# ---------------------------------------------------------------------- #
# Frontier-localized re-convergence
# ---------------------------------------------------------------------- #

@functools.partial(jax.jit, static_argnames=("n", "n_iters"))
def _compact_kernel(est_u, est_dst_masked, src, n, n_iters):
    """h-index over a pre-gathered compact frontier subproblem."""
    new = _dispatch.hindex_by_bsearch(
        est_u, _dispatch.arc_hits(est_dst_masked, src, n), n_iters)
    return new, new < est_u


# ---------------------------------------------------------------------- #
# The engine
# ---------------------------------------------------------------------- #

class StreamingKCoreEngine:
    """Maintains exact core numbers of a mutating graph.

    ``__init__`` pays one static decomposition; every ``apply_batch`` then
    re-converges incrementally from the previous fixpoint. ``self.core`` is
    exact after every batch (tested against the BZ oracle).

    Pass ``mesh`` (+ ``axis_names``) to run mesh-native: the initial
    decomposition uses the sharded static engine and churn batches with a
    ``sharded``/``auto`` frontier iterate the masked shard_map superstep.
    All execution modes are exact-equal in cores AND message counts, so a
    mesh never changes an answer — only where the work runs.
    """

    def __init__(self, g: Graph, config: StreamingConfig = StreamingConfig(),
                 kcore_config: KCoreConfig = KCoreConfig(),
                 mesh=None, axis_names=("data",)):
        if config.frontier not in FRONTIER_MODES:
            raise ValueError(f"unknown frontier mode {config.frontier!r}")
        if config.frontier == "sharded" and mesh is None:
            from repro.distribution.compat import make_mesh
            mesh = make_mesh((jax.device_count(),), ("data",))
            axis_names = ("data",)
        self.config = config
        self.mesh = mesh
        self.axis_names = tuple(axis_names)
        self._csr = PatchableCSR(g, slack=config.slack,
                                 min_slack=config.min_slack,
                                 compact_dead_frac=config.compact_dead_frac)
        self._graph_cache: Graph | None = g
        self._slots_cache: tuple | None = None
        self._live_cache: tuple | None = None
        # shape high-water marks (see _padded_slots / _run_fused): per-batch
        # fluctuations must never SHRINK a jitted program's shape
        self._arc_pad_hwm = _next_pow2(max(int(config.min_arc_capacity), 1))
        self._shard_A_floor = 0
        self._n_iters_hwm = 0
        if mesh is not None and config.frontier in ("sharded", "fused",
                                                    "auto"):
            # sharded init: same cores/messages as the single-device static
            # engine (tests/test_distributed.py), no host-side detour
            init = kcore_decompose_sharded(g, mesh, self.axis_names,
                                           max_rounds=kcore_config.max_rounds)
        else:
            init = kcore_decompose(g, kcore_config)
        self.core = init.core.astype(np.int32)
        self.init_result = init
        self.batches_applied = 0

    # ------------------------------------------------------------------ #
    @property
    def graph(self) -> Graph:
        """The current graph, materialized lazily (O(m log m)) and cached.

        The engine itself never consumes this — supersteps and seeding run
        on the patched CSR slot arrays; this is for callers (oracles,
        benchmarks, churn samplers)."""
        if self._graph_cache is None:
            self._graph_cache = self._csr.to_graph()
        return self._graph_cache

    @property
    def csr(self) -> PatchableCSR:
        return self._csr

    @property
    def n(self) -> int:
        """Vertex count — O(1), no Graph materialization."""
        return self._csr.n

    @property
    def m(self) -> int:
        """Edge count — O(1), no Graph materialization."""
        return self._csr.m

    def _live_arrays(self) -> tuple:
        """(src, dst) of the LIVE arcs only, still src-sorted (row-major
        slot order survives boolean filtering), cached until the next batch
        mutates the CSR. One O(capacity) extraction buys every downstream
        device program a 2-4x smaller arc dimension than the slack+hole
        padded slot arrays."""
        if self._live_cache is None:
            csr = self._csr
            self._live_cache = (csr.src[csr.live], csr.dst[csr.live])
        return self._live_cache

    def _padded_slots(self) -> tuple:
        """(src, dst, mask) live arc arrays padded to a pow2 HIGH-WATER
        arc count, cached until the next batch mutates the CSR. Shared by
        the seed pass and the dense/fused supersteps so their jitted
        programs see O(log) distinct arc shapes over a whole churn stream:
        the live count moves both ways batch to batch, and re-crossing a
        pow2 boundary would mint a fresh signature each time; the high-
        water mark (pre-seeded by ``min_arc_capacity``) only grows."""
        if self._slots_cache is None:
            with _trace.layer("stage", h2d_bytes=0):
                src_live, dst_live = self._live_arrays()
                k = src_live.size
                self._arc_pad_hwm = max(self._arc_pad_hwm,
                                        _next_pow2(max(k, 1)))
                arc_pad = self._arc_pad_hwm
                src_np = np.zeros(arc_pad, np.int32)
                src_np[:k] = src_live
                dst_np = np.zeros(arc_pad, np.int32)
                dst_np[:k] = dst_live
                mask = np.zeros(arc_pad, bool)
                mask[:k] = True
                self._slots_cache = (src_np, dst_np, mask)
        return self._slots_cache

    # ------------------------------------------------------------------ #
    def state_dict(self) -> dict:
        """Checkpointable pytree of the engine's exact state.

        Cores plus the full PatchableCSR slot state (``delta.PatchableCSR
        .state_dict``) — everything a warm restart needs to continue the
        stream without re-running the initial decomposition. Feed straight
        to ``repro.checkpoint.save_checkpoint``; rebuild with
        ``StreamingKCoreEngine.from_state_dict``.
        """
        return {
            "core": np.asarray(self.core, np.int32),
            "batches_applied": np.asarray(self.batches_applied, np.int64),
            "csr": self._csr.state_dict(),
            # jit-shape high-water marks: not needed for correctness, but
            # restoring them means a warm restart re-enters the stream at
            # the steady-state program shapes instead of recompiling its
            # way back up through every pow2 size
            "arc_pad_hwm": np.asarray(self._arc_pad_hwm, np.int64),
            "n_iters_hwm": np.asarray(self._n_iters_hwm, np.int64),
            "shard_A_floor": np.asarray(self._shard_A_floor, np.int64),
        }

    @classmethod
    def from_state_dict(cls, state: dict,
                        config: StreamingConfig = StreamingConfig(),
                        mesh=None, axis_names=("data",)
                        ) -> "StreamingKCoreEngine":
        """Warm-restart an engine from ``state_dict`` output.

        No decomposition runs: the restored cores ARE the fixpoint of the
        restored CSR (the pair was captured atomically), so the engine
        resumes exactly where the checkpointed one stopped. Restored
        leaves may be jnp arrays (``repro.checkpoint`` restores onto
        device) — everything is normalized back to host numpy here.
        """
        if config.frontier not in FRONTIER_MODES:
            raise ValueError(f"unknown frontier mode {config.frontier!r}")
        if config.frontier == "sharded" and mesh is None:
            from repro.distribution.compat import make_mesh
            mesh = make_mesh((jax.device_count(),), ("data",))
            axis_names = ("data",)
        eng = cls.__new__(cls)
        eng.config = config
        eng.mesh = mesh
        eng.axis_names = tuple(axis_names)
        eng._csr = PatchableCSR.from_state(
            {k: np.asarray(v) for k, v in state["csr"].items()},
            slack=config.slack, min_slack=config.min_slack,
            compact_dead_frac=config.compact_dead_frac)
        eng._graph_cache = None
        eng._slots_cache = None
        eng._live_cache = None
        eng._arc_pad_hwm = max(
            _next_pow2(max(int(config.min_arc_capacity), 1)),
            int(np.asarray(state.get("arc_pad_hwm", 1))))
        eng._shard_A_floor = int(np.asarray(state.get("shard_A_floor", 0)))
        eng._n_iters_hwm = int(np.asarray(state.get("n_iters_hwm", 0)))
        eng.core = np.asarray(state["core"], np.int32)
        eng.init_result = None
        eng.batches_applied = int(np.asarray(state["batches_applied"]))
        return eng

    # ------------------------------------------------------------------ #
    def _resolve_mode(self, n: int, active: np.ndarray) -> str:
        """Config frontier -> the execution mode this batch runs in.

        ``fused`` resolves to its mesh variant (``fused_sharded``) when a
        mesh is attached; ``auto`` picks compact below the frontier-size
        threshold and the fused path above it (device-resident while_loop
        beats per-round host dispatch whenever the frontier stays large
        for many rounds)."""
        mode = self.config.frontier
        if mode == "auto":
            frac = float(active.sum()) / max(n, 1)
            if frac <= self.config.compact_threshold:
                return "compact"
            mode = "fused"
        if mode == "fused" and self.mesh is not None:
            return "fused_sharded"
        return mode

    def _make_step(self, mode: str, n: int, n_iters: int):
        """Build the per-round step(est, active) -> (new_est, changed, recv)
        for one batch. All three implementations are exact-equal."""
        csr = self._csr
        src, dst, live, deg = csr.src, csr.dst, csr.live, csr.deg

        if mode == "dense":
            src_p, dst_p, amask_p = self._padded_slots()
            # segment-sum route only (ell=None): the slot arrays are
            # masked/mutable, not a static fully-live adjacency; the
            # operands are re-staged per batch
            prog = _dispatch.masked_round_program(
                n, n_iters, _dispatch.resolve_plan(), src_p, dst_p)
            amask_j = jnp.asarray(amask_p)

            def step(est, active):
                # est stays device-resident across rounds (the loop treats
                # it opaquely); only the small bool masks come back to host
                new_j, ch_j, recv_j = prog(
                    jnp.asarray(est), amask_j, jnp.asarray(active))
                return new_j, np.asarray(ch_j), np.asarray(recv_j)

            return step

        if mode == "compact":
            def step(est, active):
                act_ids = np.flatnonzero(active)
                if act_ids.size == 0:
                    z = np.zeros(n, bool)
                    return est, z, z
                arc_sel = live & active[src]
                sub_src = np.searchsorted(
                    act_ids, src[arc_sel]).astype(np.int32)
                sub_dst_est = est[dst[arc_sel]].astype(np.int32)

                n_act_pad = _next_pow2(act_ids.size)
                arc_pad = _next_pow2(max(sub_src.size, 1))
                est_u = np.zeros(n_act_pad, np.int32)
                est_u[: act_ids.size] = est[act_ids]
                src_pad = np.full(arc_pad, n_act_pad - 1, np.int32)
                src_pad[: sub_src.size] = sub_src
                dst_est_pad = np.zeros(arc_pad, np.int32)  # 0 never counts
                dst_est_pad[: sub_src.size] = sub_dst_est

                new_sub, changed_sub = _compact_kernel(
                    jnp.asarray(est_u), jnp.asarray(dst_est_pad),
                    jnp.asarray(src_pad), n_act_pad, n_iters)

                new_est = est.copy()
                new_est[act_ids] = np.asarray(new_sub)[: act_ids.size]
                changed = np.zeros(n, bool)
                changed[act_ids] = np.asarray(changed_sub)[: act_ids.size]
                recv = _receivers_arrays(n, src, dst, live, changed)
                return new_est, changed, recv

            return step

        # sharded: shard the slot arrays (already src-sorted — no sort) and
        # iterate the masked shard_map superstep
        sg = self._shard_slots(n)
        superstep, _ = make_sharded_superstep(sg, self.mesh, self.axis_names,
                                              n_iters, masked=True)
        n_dev = sg.n_shards
        V, n_pad = sg.verts_per_shard, sg.n_pad
        src_j = jnp.asarray(sg.src)
        dst_j = jnp.asarray(sg.dst)
        amask_j = jnp.asarray(sg.arc_mask)
        deg_j = jnp.asarray(sg.deg)

        def step(est, active):
            est_p = np.zeros(n_pad, np.int32)
            est_p[:n] = est
            act_p = np.zeros(n_pad, bool)
            act_p[:n] = active
            new_j, ch_j, recv_j, _msgs = superstep(
                jnp.asarray(est_p.reshape(n_dev, V)), src_j, dst_j, amask_j,
                deg_j, jnp.asarray(act_p.reshape(n_dev, V)))
            new = np.asarray(new_j).reshape(-1)[:n]
            ch = np.asarray(ch_j).reshape(-1)[:n]
            recv = np.asarray(recv_j).reshape(-1)[:n]
            return new, ch, recv

        return step

    def _shard_slots(self, n: int):
        """Shard the CSR slot arrays over the mesh with the arc-block
        high-water floor applied (src-sorted by construction — no sort)."""
        from repro.graph.partition import shard_arc_arrays

        src_live, dst_live = self._live_arrays()
        n_dev = int(np.prod([self.mesh.shape[a] for a in self.axis_names]))
        sg = shard_arc_arrays(n, src_live, dst_live,
                              np.ones(src_live.size, bool), self._csr.deg,
                              n_dev, pow2=True,
                              min_arcs_per_shard=self._shard_A_floor)
        self._shard_A_floor = max(self._shard_A_floor, sg.arcs_per_shard)
        return sg

    def _run_fused(self, seed: np.ndarray, active: np.ndarray, n: int,
                   n_iters: int, cap: int, sharded: bool):
        """One fused device-resident re-convergence through the shared
        runtime (core/runtime.py) — the same layer the static engine's
        ``kcore_decompose(..., fused=True)`` calls. Returns a FusedOutcome
        whose three int64 arrays cover exactly the productive rounds — the
        host-loop modes' accounting."""
        if sharded:
            sg = self._shard_slots(n)
            return fused_converge_sharded(seed, active, sg, self.mesh,
                                          self.axis_names, n=n,
                                          n_iters=n_iters, max_rounds=cap)
        src_p, dst_p, amask_p = self._padded_slots()
        return fused_converge_dense(seed, active, src_p, dst_p, amask_p,
                                    self._csr.deg, n=n, n_iters=n_iters,
                                    max_rounds=cap)

    # ------------------------------------------------------------------ #
    def apply_batch(self, batch: EdgeBatch) -> BatchResult:
        """Apply one churn batch and re-converge to exact cores.

        Each batch is a ``batch`` layer span (repro.obs.trace.layer) with
        ``csr-patch`` / ``seed`` / ``converge`` / ``host-reconstruct``
        children (``seed`` nests the tight bound's ``upper-bound`` span,
        when the batch takes it, and the initial frontier's ``frontier``;
        the fused modes nest the runtime's ``fused-converge`` ->
        ``device-converge`` / ``stats-reconstruct`` tree under
        ``converge``, and padding the slot arrays is a ``stage`` span
        wherever it first happens). Their durations are
        ``BatchResult.patch_s`` / ``seed_s`` / ``converge_s`` /
        ``reconstruct_s``. With tracing enabled they are exported too, and
        fresh XLA compiles land as ``xla.compile`` events wherever they
        happened.
        """
        with _trace.layer("batch", batch_id=self.batches_applied) as bsp:
            res = self._apply_batch_body(batch)
            bsp.set(mode=res.mode, rounds=res.rounds,
                    messages=res.stats.total_messages,
                    converged=res.converged,
                    seed_strategy=res.seed_strategy,
                    region=res.region_size,
                    recompiles=res.recompiles,
                    compile_s=round(res.compile_s, 6))
        return res

    def _apply_batch_body(self, batch: EdgeBatch) -> BatchResult:
        compiles0, csecs0 = compile_count(), compile_seconds()
        with _trace.layer("csr-patch") as patch:
            delta = self._csr.apply_batch(batch)
        self._graph_cache = None
        self._slots_cache = None
        self._live_cache = None
        csr = self._csr
        n = csr.n
        deg64 = csr.deg.astype(np.int64)

        with _trace.layer("seed") as ssp:
            old_core_ext = np.zeros(n, np.int64)
            old_core_ext[: self.core.shape[0]] = self.core
            seed_choice = choose_seed(delta.inserted, csr.deg, old_core_ext,
                                      model=self.config.seed_model)
            if seed_choice.strategy == "degree":
                # bulk load: degree seed (see StreamingConfig.seed_model)
                U = deg64.copy()
            else:
                src_p, dst_p, live_p = self._padded_slots()
                U, _ = _insertion_upper_bound_arrays(n, src_p, dst_p, live_p,
                                                     csr.deg, old_core_ext,
                                                     delta.inserted)
            seed = np.minimum(U, deg64).astype(np.int32)
            region = U > old_core_ext
            old_core32 = old_core_ext.astype(np.int32)

            # ---- round 0: seed broadcast + link handshakes ------------ #
            seed_changed = seed != old_core32
            msgs = [int(deg64[seed_changed].sum())
                    + 2 * int(delta.inserted.shape[0])
                    + 2 * int(delta.deleted.shape[0])]
            changed_counts = [int(seed_changed.sum())]

            # ---- initial frontier ------------------------------------- #
            # recompute u iff its h-index inputs changed: an incident edge
            # appeared/disappeared, or a neighbor's broadcast value changed.
            with _trace.layer("frontier"):
                active = np.zeros(n, bool)
                touched = delta.touched[delta.touched < n]
                active[touched] = True
                active |= seed_changed
                src_live, dst_live = self._live_arrays()
                active |= _receivers_arrays(n, src_live, dst_live, None,
                                            seed_changed)
            ssp.set(strategy=seed_choice.strategy,
                    region=int(region.sum()),
                    frontier=int(active.sum()))
        # active_per_round follows the static engine's convention:
        # [r] = vertices recomputing/broadcasting in round r. Round 0 is the
        # seed rebroadcast; round 1's recomputers are the initial frontier.
        actives = [int(seed_changed.sum()), int(active.sum())]

        mode = self._resolve_mode(n, active)
        # flight: one run per churn batch; round 0 = seed rebroadcast +
        # link handshakes. No prev_est on round 0 — seed vs the old core
        # legitimately moves both ways, only rounds >= 1 must be monotone.
        rec = _flight.recorder()
        if rec.active:
            rec.start_run("streaming", mode, batch=self.batches_applied, n=n)
            rec.record_round(actives[0], msgs[0], changed_counts[0],
                             est=seed)
        est = seed
        rounds, converged = 0, False
        cap = (self.config.max_rounds if self.config.max_rounds is not None
               else n + 1)
        # the binary-search depth is bucketed (multiple of 4) and high-water-
        # marked: extra iterations are idempotent at the h-index fixpoint,
        # so neither a shrinking max degree nor one that creeps up by single
        # bits may mint a fresh jit signature
        n_iters = _round_up(_bs_iters(int(csr.deg.max()) if n else 0), 4)
        n_iters = self._n_iters_hwm = max(n_iters, self._n_iters_hwm)

        with _trace.layer("converge", mode=mode) as conv:
            if mode in ("fused", "fused_sharded"):
                if active.any():
                    outcome = self._run_fused(seed, active, n, n_iters, cap,
                                              sharded=mode == "fused_sharded")
                    core, rounds = outcome.est, outcome.rounds
                    converged = outcome.converged
                    msgs.extend(outcome.msgs.tolist())
                    changed_counts.extend(outcome.changed.tolist())
                    actives.extend(outcome.recv.tolist())
                else:
                    core, converged = np.asarray(seed, np.int32), True
            else:
                step = self._make_step(mode, n, n_iters)
                while rounds < cap and active.any():
                    t_r = time.perf_counter() if rec.active else 0.0
                    with _trace.span("kcore.round", round=rounds):
                        new_est, ch, recv = step(est, active)
                        rounds += 1
                        if not ch.any():
                            converged = True
                            break
                        msgs.append(int(deg64[ch].sum()))
                        changed_counts.append(int(ch.sum()))
                        if rec.active:
                            rec.record_round(
                                actives[rounds], msgs[-1],
                                changed_counts[-1],
                                est=np.asarray(new_est),
                                prev_est=np.asarray(est),
                                host_s=time.perf_counter() - t_r)
                        active = recv
                        actives.append(int(active.sum()))
                        est = new_est
                if not active.any():
                    converged = True
                core = np.asarray(est, np.int32)

        with _trace.layer("host-reconstruct") as rec_span:
            stats = MessageStats(
                messages_per_round=np.asarray(msgs, np.int64),
                active_per_round=np.asarray(actives[: len(msgs)], np.int64),
                changed_per_round=np.asarray(changed_counts[: len(msgs)],
                                             np.int64),
            )
            self.core = core
            self.batches_applied += 1
            cap_slots = max(csr.capacity, 1)
            if rec.active:
                rec.end_run(converged=converged,
                            messages=int(stats.total_messages))
            res = BatchResult(core=core, rounds=rounds, converged=converged,
                              stats=stats, delta=delta,
                              region_size=int(region.sum()),
                              seed_changed=int(seed_changed.sum()),
                              mode=mode, patch_s=patch.seconds,
                              seed_s=ssp.seconds, converge_s=conv.seconds,
                              seed_strategy=seed_choice.strategy,
                              seed_est_passes=seed_choice.est_passes,
                              recompiles=compile_count() - compiles0,
                              compile_s=compile_seconds() - csecs0,
                              csr_compactions=int(csr.compactions),
                              csr_dead_frac=csr.dead / cap_slots,
                              csr_occupancy=2 * csr.m / cap_slots)
        res.reconstruct_s = rec_span.seconds
        return res
