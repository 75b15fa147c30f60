"""Distributed k-core decomposition — the paper's algorithm, TPU-native.

Montresor-style locality iteration: every vertex keeps a monotonically
decreasing estimate, initialized to its degree; each round it recomputes

    est'(u) = H( { min(est(v), est(u)) : v in adj(u) } )

where H is the h-index operator, and "sends" its new value to all neighbors
when it decreased. The fixpoint equals the exact core numbers (locality
theorem, §II.B of the paper).

Execution modes
  * ``jacobi``    — paper-faithful synchronous rounds (every vertex updates
                    from last round's estimates).
  * ``block_gs``  — beyond-paper block-Gauss-Seidel: vertex blocks are swept
                    sequentially within a round using freshest estimates;
                    converges in fewer rounds / messages (mimics the Go
                    version's asynchrony).

Execution paths
  * host loop   — one jitted superstep per Python-loop round;
  * fused       — the whole round loop as one device-resident while_loop
                  (the shared runtime, core/runtime.py); jacobi only.
  Both run the one single-device round body, ``core.dispatch._round``; the
  plan (``KCoreConfig.dispatch``, from the platform by default) picks its
  reductions: XLA segment sums, or the Pallas kernels (on a from-scratch
  decomposition the ELL ``kcore_hindex`` kernel does the whole h-index).
  ``block_gs`` sweeps its blocks with the XLA segment sums.

Distribution: `make_sharded_superstep` builds a shard_map superstep over a
device mesh — vertex state sharded by contiguous range, arcs co-located with
their source, one `all_gather` of the estimate vector per round (this IS the
paper's message broadcast), counts purely local, termination = 1-bit psum.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import dispatch as _dispatch
from repro.core.jit_telemetry import compile_count, compile_seconds
from repro.core.messages import MessageStats
from repro.obs import flight as _flight
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace
from repro.graph.partition import ShardedGraph
from repro.graph.structs import Graph, build_ell


# ---------------------------------------------------------------------- #
# Config / result
# ---------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class KCoreConfig:
    mode: str = "jacobi"            # "jacobi" | "block_gs"
    n_blocks: int = 8               # block_gs sweep granularity
    max_rounds: int | None = None   # None → n (the worst-case depth)
    widths: tuple[int, ...] = (8, 32, 128, 512, 2048)
    # run the whole round loop as ONE device-resident lax.while_loop via the
    # shared fused runtime (core/runtime.py) instead of one jitted superstep
    # per Python-loop round. jacobi only; accounting is bit-equal either way.
    fused: bool = False
    # superstep kernel dispatch (repro.core.dispatch): "auto" consults the
    # platform layer (REPRO_PALLAS env; Pallas only where it compiles
    # natively), "pallas"/"xla" force it. Jacobi paths (host loop and
    # fused) only; execution placement, never accounting.
    dispatch: str = "auto"


@dataclasses.dataclass
class KCoreResult:
    core: np.ndarray
    rounds: int
    converged: bool
    stats: MessageStats
    # fresh XLA compilations this decomposition caused (process-wide delta
    # of repro.core.jit_telemetry.compile_count; 0 = every jitted program
    # was a cache hit) — makes the fused path's O(log)-compiles claim
    # measurable in benchmarks/static_decomposition.py
    recompiles: int = 0
    # ... and the wall-clock XLA spent on those compiles (the duration-
    # valued twin: jit_telemetry.compile_seconds delta)
    compile_s: float = 0.0
    # per-phase wall breakdown (seconds), the durations of the layer spans
    # (repro.obs.trace.layer). Fused runs report the runtime's split:
    # "device-converge" (staging and the while_loop, blocked to
    # completion), "host-reconstruct" (stats recovery), and "stage" (every
    # host staging span: ELL layout, blocked layout, H2D copies) and
    # "device-loop" (the while_loop alone); host-loop runs report
    # "converge" (the whole round loop).
    phase_s: dict = dataclasses.field(default_factory=dict)
    # resolved superstep dispatch this run executed with ("xla" | "pallas");
    # see repro.core.dispatch — bills are bit-equal across choices
    dispatch: str = "xla"
    # fused runs: ids of the devices that held the final estimate's shards
    # (FusedOutcome.devices); empty for the host round loops
    devices: tuple = ()


def _bs_iters(max_deg: int) -> int:
    """Static binary-search iteration count covering estimates in [0, maxdeg]."""
    return max(int(np.ceil(np.log2(max_deg + 1))) + 1, 1)


# ---------------------------------------------------------------------- #
# Fused convergence — per-round accounting from the device stat buffers
# ---------------------------------------------------------------------- #

def fused_round_stats(rounds, stopped, final_active,
                      msgs_buf, changed_buf, recv_buf):
    """Host-side reconstruction of per-round accounting from fused buffers.

    Returns ``(k, msgs, changed, recv, converged)``: ``k`` is the number of
    PRODUCTIVE rounds (the prefix whose changed count is non-zero — once a
    round changes nothing the loop stops, so productive rounds are always a
    prefix) and the three ``(k,)`` int64 arrays are exactly what the
    host-loop modes would have appended round by round.
    """
    rounds = int(rounds)
    cb = np.asarray(changed_buf[:rounds], np.int64)
    k = int((cb > 0).sum())
    converged = bool(stopped) or int(final_active) == 0
    return (k, np.asarray(msgs_buf[:k], np.int64), cb[:k],
            np.asarray(recv_buf[:k], np.int64), converged)


@functools.lru_cache(maxsize=64)
def _fused_sharded_convergence(mesh: jax.sharding.Mesh, axes: tuple,
                               V: int, n_iters: int, max_rounds: int):
    """Cached jitted fused convergence over a device mesh (streaming path).

    The masked shard_map superstep (``_sharded_round``) iterated by the
    single-device loop, ``dispatch.converge_loop``, with its counts summed
    over the mesh: the whole batch re-convergence is one shard_map
    program, with per-round cross-device traffic only (one est all_gather,
    one 1-bit changed all_gather, scalar psums) — the host sees the final
    estimate plus the filled stat buffers, same contract and same exact
    accounting as ``dispatch.fused_convergence_program``. Keyed on (mesh,
    axes, V, n_iters, max_rounds) like its per-round sibling so stable
    shard shapes reuse one compiled program across batches.

    Returns ``prog(est, src, dst, arc_mask, deg, active) -> (est', rounds,
    stopped, final_active, msgs_buf, changed_buf, recv_buf)`` with est'
    sharded like the state and everything else replicated.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distribution.compat import shard_map

    def prog(est, src, dst, arc_mask, deg, active):
        # shapes inside shard_map (per device): est (1, V), src (1, A), ...
        src_l, dst_l, am_l = src[0], dst[0], arc_mask[0]

        def step(est_c, act_c):
            new_l, changed_l, recv_l = _sharded_round(
                est_c, act_c, src_l, dst_l, am_l, axes, V, n_iters)
            return new_l[None], changed_l, recv_l[None]

        return _dispatch.converge_loop(
            step, est, active, deg[0], max_rounds=max_rounds,
            any_=lambda x: lax.psum(jnp.sum(x, dtype=jnp.int32), axes) > 0,
            total=lambda x: lax.psum(x, axes))

    spec_state = P(axes)
    sharded = shard_map(prog, mesh=mesh, in_specs=(spec_state,) * 6,
                        out_specs=(spec_state,) + (P(),) * 6)
    return jax.jit(sharded)


# ---------------------------------------------------------------------- #
# Single-host rounds — block-Gauss-Seidel (beyond-paper)
# ---------------------------------------------------------------------- #

def _make_round_block_gs(sg: ShardedGraph, n_iters: int):
    src = jnp.asarray(sg.src)          # (B, A) local indices
    dst = jnp.asarray(sg.dst)          # (B, A) global indices
    amask = jnp.asarray(sg.arc_mask)
    B, V = sg.n_shards, sg.verts_per_shard
    n_pad = sg.n_pad

    @jax.jit
    def round_gs(est):
        """est: (n_pad,) int32. Sweeps blocks 0..B-1 with fresh estimates."""
        def block_body(b, carry):
            est, changed = carry
            est_dst = jnp.where(amask[b], est[dst[b]], 0)
            est_u = lax.dynamic_slice(est, (b * V,), (V,))
            new_u = _dispatch.hindex_by_bsearch(
                est_u, _dispatch.arc_hits(est_dst, src[b], V), n_iters)
            ch_u = new_u < est_u
            est = lax.dynamic_update_slice(est, new_u, (b * V,))
            changed = lax.dynamic_update_slice(changed, ch_u, (b * V,))
            return est, changed

        changed0 = jnp.zeros(n_pad, bool)
        est, changed = lax.fori_loop(0, B, block_body, (est, changed0))
        return est, changed

    return round_gs


# ---------------------------------------------------------------------- #
# Driver
# ---------------------------------------------------------------------- #

def kcore_decompose(g: Graph, config: KCoreConfig = KCoreConfig(), *,
                    fused: bool | None = None) -> KCoreResult:
    """Run distributed k-core decomposition to the fixpoint on one host.

    Per-round message/active accounting follows the paper exactly (see
    core/messages.py). By default the Python loop is over rounds only; each
    round is one jitted superstep. With ``fused=True`` (keyword override of
    ``config.fused``) the ENTIRE round loop runs as one device-resident
    ``lax.while_loop`` through the shared fused runtime (core/runtime.py) —
    no per-round host round-trips — and the per-round stats are
    reconstructed from device buffers, bit-equal to the host loop
    (hypothesis-tested, BZ-verified). Fused is jacobi-only.
    """
    use_fused = config.fused if fused is None else fused
    if use_fused and config.mode != "jacobi":
        raise ValueError("fused=True requires mode='jacobi' "
                         f"(got {config.mode!r})")
    with _trace.layer("kcore.decompose", n=g.n, m=g.m, mode=config.mode,
                      fused=bool(use_fused)) as _sp:
        res = _decompose_body(g, config, use_fused)
        _sp.set(rounds=res.rounds, messages=res.stats.total_messages,
                converged=res.converged, recompiles=res.recompiles,
                compile_s=round(res.compile_s, 6), dispatch=res.dispatch)
    return res


def _decompose_body(g: Graph, config: KCoreConfig,
                    use_fused: bool) -> KCoreResult:
    compiles0, csecs0 = compile_count(), compile_seconds()
    phase_s: dict = {}
    devices: tuple = ()
    n = g.n
    if n == 0:
        return KCoreResult(core=np.zeros(0, np.int32), rounds=0,
                           converged=True,
                           stats=MessageStats(*(np.zeros(0, np.int64),) * 3))
    if config.mode not in ("jacobi", "block_gs"):
        raise ValueError(f"unsupported mode {config.mode!r}")
    # block_gs sweeps with the XLA segment sums whatever the plan
    plan = _dispatch.resolve_plan(
        config.dispatch if config.mode == "jacobi" else "xla")
    n_iters = _bs_iters(g.max_deg)
    max_rounds = config.max_rounds if config.max_rounds is not None else n + 1
    deg64 = g.deg.astype(np.int64)

    msgs = [int(deg64.sum())]             # round 0: degree broadcast = 2m
    # active[r] = vertices recomputing in round r. Round 0: all (they all
    # broadcast); round 1: every vertex that received the degree broadcast.
    active = [n, int((g.deg > 0).sum())]
    changed_counts = [n]

    # flight recorder: one run per decomposition, round 0 = the degree
    # broadcast. Disabled path = one attribute read; every est host-copy
    # and per-round clock below is guarded by rec.active.
    rec = _flight.recorder()
    if rec.active:
        rec.start_run(
            "static",
            "fused" if use_fused else f"{config.mode}/{plan.kind}",
            n=n)
        rec.record_round(active[0], msgs[0], changed_counts[0], est=g.deg)

    ell = None
    if config.mode == "jacobi":
        with _trace.layer("stage", h2d_bytes=0) as ell_stage:
            if plan.kind == "pallas":
                # static fully-live adjacency + degree seed: the ELL
                # h-index route is exact here (see dispatch._round)
                ell = build_ell(g, widths=config.widths)
            arc_mask = np.ones(g.num_arcs, bool)

    if use_fused:
        from repro.core.runtime import fused_converge_dense

        # from-scratch seeding: est = degrees, frontier = every vertex —
        # round 1 of the fused loop IS round 1 of the host loop, and the
        # recv-masked rounds after it are exact for the monotone locality
        # operator (an inactive vertex's inputs are unchanged)
        # frontier1: the while_loop activates everyone but the accounting
        # bills only (deg>0) receivers in round 1 — pass the accounting
        # value so flight records match the host loop bit-for-bit
        outcome = fused_converge_dense(
            g.deg, np.ones(n, bool), g.src, g.dst, arc_mask, g.deg,
            n=n, n_iters=n_iters, max_rounds=max_rounds,
            dispatch=plan.kind, ell=ell, frontier1=active[1])
        rounds, converged = outcome.rounds, outcome.converged
        devices = outcome.devices
        msgs.extend(outcome.msgs.tolist())
        changed_counts.extend(outcome.changed.tolist())
        active.extend(outcome.recv.tolist())
        core = outcome.est
        phase_s["device-converge"] = outcome.device_s
        phase_s["host-reconstruct"] = outcome.reconstruct_s
        phase_s["stage"] = ell_stage.seconds + outcome.stage_s
        phase_s["device-loop"] = outcome.loop_s
    else:
        # step(est) -> (new_est, changed (n,) on the host, receiver count)
        if config.mode == "jacobi":
            prog = _dispatch.masked_round_program(
                n, n_iters, plan, g.src, g.dst, ell=ell)
            amask, everyone = jnp.asarray(arc_mask), jnp.ones(n, bool)

            def step(est):
                new_est, changed, recv = prog(est, amask, everyone)
                return new_est, np.asarray(changed), int(np.asarray(recv).sum())

            est = jnp.asarray(g.deg, jnp.int32)
        else:
            from repro.graph.partition import shard_graph

            sg = shard_graph(g, max(1, config.n_blocks))
            round_gs = _make_round_block_gs(sg, n_iters)

            def step(est):
                new_est, changed = round_gs(est)
                ch = np.asarray(changed)[:n]
                return new_est, ch, int(_receivers_np(g, ch).sum())

            est = jnp.asarray(sg.deg.reshape(-1), jnp.int32)
        rounds, converged = 0, False
        with _trace.layer("converge") as conv:
            while rounds < max_rounds:
                t_r = time.perf_counter() if rec.active else 0.0
                with _trace.span("kcore.round", round=rounds) as rsp:
                    new_est, ch_np, n_recv = step(est)
                    rounds += 1
                    if not ch_np.any():
                        converged = True
                        break
                    msgs.append(int(deg64[ch_np].sum()))
                    changed_counts.append(int(ch_np.sum()))
                    active.append(n_recv)
                    rsp.set(messages=msgs[-1], changed=changed_counts[-1])
                    if rec.active:
                        rec.record_round(
                            active[rounds], msgs[-1], changed_counts[-1],
                            est=np.asarray(new_est)[:n],
                            prev_est=np.asarray(est)[:n],
                            host_s=time.perf_counter() - t_r,
                            dispatch=plan.kind)
                    est = new_est
        phase_s["converge"] = conv.seconds
        core = np.asarray(est)[:n].astype(np.int32)

    stats = MessageStats(
        messages_per_round=np.asarray(msgs, np.int64),
        active_per_round=np.asarray(active[: len(msgs)], np.int64),
        changed_per_round=np.asarray(changed_counts[: len(msgs)], np.int64),
    )
    if rec.active:
        rec.end_run(converged=converged, messages=int(stats.total_messages))
    return KCoreResult(core=core, rounds=rounds, converged=converged,
                       stats=stats,
                       recompiles=compile_count() - compiles0,
                       compile_s=compile_seconds() - csecs0,
                       phase_s=phase_s, dispatch=plan.kind,
                       devices=devices)


def _receivers_arrays(n: int, src: np.ndarray, dst: np.ndarray,
                      live: np.ndarray | None, changed: np.ndarray
                      ) -> np.ndarray:
    """Vertices with a (live) arc to a changed vertex — the next frontier.

    ``live`` is an optional arc mask (the streaming engine's slack-padded
    CSR has dead slots); None means every arc is real.
    """
    recv = np.zeros(n, bool)
    if changed.any():
        sel = changed[dst] if live is None else live & changed[dst]
        np.logical_or.at(recv, src[sel], True)
    return recv


def _receivers_np(g: Graph, changed: np.ndarray) -> np.ndarray:
    return _receivers_arrays(g.n, g.src, g.dst, None, changed)


# ---------------------------------------------------------------------- #
# Sharded superstep (shard_map) — the multi-pod path
# ---------------------------------------------------------------------- #

def _sharded_round(est, active, src, dst, arc_mask, axes, V, n_iters):
    """One frontier-masked superstep inside shard_map, on one device's
    block: ``est``/``active`` (1, V), ``src`` (local ids), ``dst`` (global
    ids) and ``arc_mask`` its arcs. All-gathers the estimates, runs the
    binary-search h-index with the local segment sum, then all-gathers the
    1-bit changed mask to find next round's receivers locally. Returns
    ``(new, changed, recv)``, each (V,)."""
    est_l = est[0]
    est_glob = lax.all_gather(est, axes, axis=0, tiled=True).reshape(-1)
    est_dst = jnp.where(arc_mask, est_glob[dst], 0)
    h = _dispatch.hindex_by_bsearch(
        est_l, _dispatch.arc_hits(est_dst, src, V), n_iters)
    new_l = jnp.where(active[0], h, est_l)
    changed_l = new_l < est_l
    ch_glob = lax.all_gather(changed_l[None], axes, axis=0,
                             tiled=True).reshape(-1)
    recv_l = jax.ops.segment_sum(
        jnp.where(arc_mask, ch_glob[dst], False).astype(jnp.int32),
        src, num_segments=V) > 0
    return new_l, changed_l, recv_l


@functools.lru_cache(maxsize=128)
def _masked_sharded_superstep(mesh: jax.sharding.Mesh,
                              axes: tuple, V: int, n_iters: int):
    """Cached jitted frontier-masked sharded superstep (streaming path).

    Keyed on (mesh, axes, verts_per_shard, n_iters) so a churn stream whose
    shard shapes are stable (the engine pads them to powers of two) reuses
    one compiled program across batches. Same layout contract as
    ``make_sharded_superstep``; on top of the est all_gather a second 1-bit
    all_gather of the changed mask computes next round's receivers locally
    (``_sharded_round``).

    Returns ``superstep(est, src, dst, arc_mask, deg, active) ->
    (est', changed, recv, msgs)`` with est'/changed/recv sharded like the
    state and msgs a replicated scalar.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distribution.compat import shard_map

    def superstep(est, src, dst, arc_mask, deg, active):
        new_l, changed_l, recv_l = _sharded_round(
            est, active, src[0], dst[0], arc_mask[0], axes, V, n_iters)
        msgs = lax.psum(jnp.sum(jnp.where(changed_l, deg[0], 0)), axes)
        return new_l[None], changed_l[None], recv_l[None], msgs

    spec_state = P(axes)
    sharded = shard_map(superstep, mesh=mesh,
                        in_specs=(spec_state,) * 6,
                        out_specs=(spec_state, spec_state, spec_state, P()))
    return jax.jit(sharded)


def make_sharded_superstep(sg: ShardedGraph, mesh: jax.sharding.Mesh,
                           axis_names: Sequence[str], n_iters: int,
                           masked: bool = False):
    """Build a jit-able superstep over a device mesh.

    State layout: est (n_shards, V) with the leading dim sharded over the
    flattened ``axis_names``. Per round:
      1. all_gather est over the mesh axes  — the paper's message broadcast;
      2. gather est[dst] for local arcs     — local memory traffic;
      3. log2(maxdeg) local segment_sums    — the binary-search h-index;
      4. psum of (messages, changed-any)    — the paper's heartbeat/termination.

    Returns ``superstep(est, src, dst, arc_mask, deg) -> (est', msgs, any)``
    plus the in/out shardings for jit. With ``masked=True`` the superstep
    additionally takes an ``active`` (n_shards, V) bool mask — only active
    vertices recompute — and returns ``(est', changed, recv, msgs)`` (see
    ``_masked_sharded_superstep``); this is the primitive the streaming
    engine iterates on a mesh.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    axes = tuple(axis_names)
    V = sg.verts_per_shard

    if masked:
        shardings = {
            "state": NamedSharding(mesh, P(axes)),
            "scalar": NamedSharding(mesh, P()),
        }
        return _masked_sharded_superstep(mesh, axes, V, n_iters), shardings

    def superstep(est, src, dst, arc_mask, deg):
        # shapes inside shard_map (per device): est (1, V), src (1, A), ...
        est_l = est[0]
        est_glob = lax.all_gather(est, axes, axis=0, tiled=True).reshape(-1)
        est_dst = jnp.where(arc_mask[0], est_glob[dst[0]], 0)
        new_l = _dispatch.hindex_by_bsearch(
            est_l, _dispatch.arc_hits(est_dst, src[0], V), n_iters)
        changed = new_l < est_l
        # int32 is safe per round: messages/round <= 2m < 2^31 for all graphs
        # we target; host-side totals accumulate in int64.
        msgs = lax.psum(jnp.sum(jnp.where(changed, deg[0], 0)), axes)
        any_changed = lax.psum(changed.any().astype(jnp.int32), axes) > 0
        return new_l[None], msgs, any_changed

    from repro.distribution.compat import shard_map

    spec_state = P(axes)  # leading shard dim over all mesh axes
    in_specs = (spec_state, spec_state, spec_state, spec_state, spec_state)
    out_specs = (spec_state, P(), P())
    sharded = shard_map(superstep, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs)
    shardings = {
        "state": NamedSharding(mesh, spec_state),
        "scalar": NamedSharding(mesh, P()),
    }
    return sharded, shardings


def kcore_decompose_sharded(g: Graph, mesh: jax.sharding.Mesh,
                            axis_names: Sequence[str],
                            max_rounds: int | None = None,
                            fused: bool = False) -> KCoreResult:
    """Run the sharded engine to convergence (works on any mesh incl. 1 dev).

    With ``fused=True`` the whole round loop nests the masked shard_map
    superstep inside one device-resident ``lax.while_loop`` (the shared
    fused runtime, core/runtime.py): per-round cross-device traffic only,
    no host round-trips, accounting bit-equal to the host loop.
    """
    from repro.distribution.compat import is_multiprocess_mesh
    from repro.graph.partition import shard_graph

    if is_multiprocess_mesh(mesh) and not fused:
        # the per-round host loop reads sharded device state every round
        # with process-local conversions; only the fused runtime stages
        # global arrays (runtime.fused_converge_sharded via compat)
        raise ValueError("multi-process meshes require fused=True")

    compiles0, csecs0 = compile_count(), compile_seconds()
    phase_s: dict = {}
    devices: tuple = ()
    n_dev = int(np.prod([mesh.shape[a] for a in axis_names]))
    sg = shard_graph(g, n_dev)
    # straggler visibility: a round's wall is the slowest shard's, so skew
    # should be observable BEFORE it costs wall-clock (same metric the
    # out-of-core driver publishes per block store)
    from repro.graph.partition import balance_report
    _metrics.gauge("kcore_shard_imbalance").set(
        balance_report(sg)["imbalance"])
    n_iters = _bs_iters(g.max_deg)

    deg64 = g.deg.astype(np.int64)
    msgs = [int(deg64.sum())]
    active = [g.n, int((g.deg > 0).sum())]
    changed_counts = [g.n]
    cap = max_rounds if max_rounds is not None else g.n + 1

    rec = _flight.recorder()
    if rec.active:
        rec.start_run("static", "fused_sharded" if fused else "sharded",
                      n=g.n)
        rec.record_round(active[0], msgs[0], changed_counts[0], est=g.deg)

    with _trace.layer("kcore.decompose", n=g.n, m=g.m, mode="sharded",
                      mesh_devices=n_dev, fused=bool(fused)) as _sp:
        if fused:
            from repro.core.runtime import fused_converge_sharded

            outcome = fused_converge_sharded(
                g.deg, np.ones(g.n, bool), sg, mesh, tuple(axis_names),
                n=g.n, n_iters=n_iters, max_rounds=cap,
                frontier1=active[1])
            rounds, converged = outcome.rounds, outcome.converged
            devices = outcome.devices
            msgs.extend(outcome.msgs.tolist())
            changed_counts.extend(outcome.changed.tolist())
            active.extend(outcome.recv.tolist())
            core = outcome.est
            phase_s["device-converge"] = outcome.device_s
            phase_s["host-reconstruct"] = outcome.reconstruct_s
            phase_s["stage"] = outcome.stage_s
            phase_s["device-loop"] = outcome.loop_s
        else:
            superstep, _ = make_sharded_superstep(sg, mesh, axis_names, n_iters)
            superstep = jax.jit(superstep)

            est = jnp.asarray(sg.deg, jnp.int32)
            src = jnp.asarray(sg.src)
            dst = jnp.asarray(sg.dst)
            amask = jnp.asarray(sg.arc_mask)
            deg = jnp.asarray(sg.deg)

            rounds, converged = 0, False
            with _trace.layer("converge") as conv:
                while rounds < cap:
                    t_r = time.perf_counter() if rec.active else 0.0
                    with _trace.span("kcore.round", round=rounds) as rsp:
                        new_est, m, any_ch = superstep(est, src, dst, amask, deg)
                        rounds += 1
                        if not bool(any_ch):
                            converged = True
                            break
                        ch_real = np.asarray(new_est < est).reshape(-1)[: g.n]
                        msgs.append(int(m))
                        changed_counts.append(int(ch_real.sum()))
                        active.append(int(_receivers_np(g, ch_real).sum()))
                        rsp.set(messages=msgs[-1], changed=changed_counts[-1])
                        if rec.active:
                            rec.record_round(
                                active[rounds], msgs[-1], changed_counts[-1],
                                est=np.asarray(new_est).reshape(-1)[: g.n],
                                prev_est=np.asarray(est).reshape(-1)[: g.n],
                                host_s=time.perf_counter() - t_r)
                        est = new_est
            phase_s["converge"] = conv.seconds
            core = np.asarray(est).reshape(-1)[: g.n].astype(np.int32)
        _sp.set(rounds=rounds, converged=converged,
                messages=int(np.asarray(msgs, np.int64).sum()))
    stats = MessageStats(np.asarray(msgs, np.int64),
                         np.asarray(active[: len(msgs)], np.int64),
                         np.asarray(changed_counts[: len(msgs)], np.int64))
    if rec.active:
        rec.end_run(converged=converged, messages=int(stats.total_messages))
    return KCoreResult(core=core, rounds=rounds, converged=converged,
                       stats=stats,
                       recompiles=compile_count() - compiles0,
                       compile_s=compile_seconds() - csecs0,
                       phase_s=phase_s, devices=devices)
