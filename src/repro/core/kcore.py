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

Backends
  * ``segment``     — sorted-COO + jax.ops.segment_sum; the general, shardable
                      path. The per-round h-index is a vectorized binary
                      search (log2(maxdeg) segment_sums per round).
  * ``ell``         — degree-bucketed dense tiles, pure-jnp rowwise h-index.
  * ``ell_pallas``  — same layout, Pallas kernel (kernels/kcore_hindex).

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
from repro.graph.structs import EllGraph, Graph


# ---------------------------------------------------------------------- #
# Config / result
# ---------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class KCoreConfig:
    mode: str = "jacobi"            # "jacobi" | "block_gs"
    backend: str = "segment"        # "segment" | "ell" | "ell_pallas"
    n_blocks: int = 8               # block_gs sweep granularity
    max_rounds: int | None = None   # None → n (the worst-case depth)
    widths: tuple[int, ...] = (8, 32, 128, 512, 2048)
    # run the whole round loop as ONE device-resident lax.while_loop via the
    # shared fused runtime (core/runtime.py) instead of one jitted superstep
    # per Python-loop round. jacobi only; accounting is bit-equal either way.
    fused: bool = False
    # superstep kernel dispatch (repro.core.dispatch): "auto" consults the
    # platform layer (REPRO_PALLAS env; Pallas only where it compiles
    # natively), "pallas"/"xla" force it. Segment-backend jacobi paths
    # (host loop and fused) only; execution placement, never accounting.
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
# Single-host rounds — segment backend
# ---------------------------------------------------------------------- #

def _hindex_by_bsearch(est, est_dst_masked, src, n, n_iters):
    """Vectorized per-vertex h-index via binary search.

    For every vertex u, finds max k in [0, est_u] with
    |{arcs (u,v): est_v >= k}| >= k. est_dst_masked must be 0 on padding arcs
    (so they never count for k >= 1).
    """
    lo = jnp.zeros_like(est)
    hi = est

    def body(lohi, _):
        lo, hi = lohi
        mid = (lo + hi + 1) // 2
        hit = (est_dst_masked >= mid[src]) & (mid[src] > 0)
        cnt = jax.ops.segment_sum(hit.astype(jnp.int32), src, num_segments=n)
        ok = cnt >= mid
        return (jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)), None

    # lax.scan (not fori_loop): scan records the trip count in the jaxpr,
    # which the roofline's jaxpr-walk cost analysis needs to be exact.
    (lo, hi), _ = lax.scan(body, (lo, hi), None, length=n_iters)
    return lo


def _masked_round(est, src, dst, arc_mask, active, n, n_iters):
    """Traceable body of the masked Jacobi superstep (shared by the jitted
    per-round entry point and the fused while_loop)."""
    est_dst = jnp.where(arc_mask, est[dst], 0)
    h = _hindex_by_bsearch(est, est_dst, src, n, n_iters)
    new_est = jnp.where(active, h, est)
    changed = new_est < est
    # who receives a message next round: u s.t. some neighbor v changed
    recv = jax.ops.segment_sum(
        (jnp.where(arc_mask, changed[dst], False)).astype(jnp.int32),
        src, num_segments=n) > 0
    return new_est, changed, recv


@functools.partial(jax.jit, static_argnames=("n", "n_iters"))
def masked_round_segment(est, src, dst, arc_mask, active, n, n_iters):
    """One frontier-masked Jacobi superstep. Returns (new_est, changed, recv).

    Only vertices with ``active`` True recompute their h-index; everyone else
    keeps their estimate. With ``active`` all-True this is the paper's plain
    synchronous superstep. The masked form is the primitive the streaming
    engine (repro.streaming.engine) iterates: after an edge-churn batch only
    the frontier — vertices whose estimate may still drop — recomputes, which
    is exact for the monotone locality operator (an inactive vertex's inputs
    are unchanged, so recomputing it would be a no-op).
    """
    return _masked_round(est, src, dst, arc_mask, active, n, n_iters)


def _round_segment(est, src, dst, arc_mask, n, n_iters):
    """One (unmasked) Jacobi superstep. Returns (new_est, changed, received)."""
    active = jnp.ones(est.shape, bool)
    return masked_round_segment(est, src, dst, arc_mask, active, n, n_iters)


# ---------------------------------------------------------------------- #
# Fused convergence — one device-resident while_loop per batch
# ---------------------------------------------------------------------- #

@functools.partial(jax.jit, static_argnames=("n", "n_iters", "max_rounds"))
def fused_convergence(est, src, dst, arc_mask, active, deg,
                      n, n_iters, max_rounds):
    """Run masked Jacobi supersteps to the fixpoint in ONE ``lax.while_loop``.

    The host round loop (kcore_decompose / the streaming engine's per-round
    ``step``) pays a device round-trip of est/changed/recv per superstep —
    at streaming batch sizes that host traffic, not the h-index math,
    dominates wall-clock. Montresor et al. bound the number of rounds, so a
    whole batch re-convergence is a bounded iteration that can live on
    device: carry = (est, active, round_idx, stop, per-round stat buffers),
    body = the same ``_masked_round`` superstep the host loop runs, cond =
    frontier non-empty (and round cap not hit, and last round productive).

    Per executed round r the body fills three ``(max_rounds,)`` int32
    buffers — messages (Σ deg over changed vertices; < 2m < 2^31 per round
    for every graph we target, accumulated to int64 on host), changed
    count, and receiver count — from which the host reconstructs per-round
    ``MessageStats`` EXACTLY equal to the host-loop modes' accounting
    (see ``fused_round_stats``).

    Returns ``(est', rounds, stopped, final_active, msgs_buf, changed_buf,
    recv_buf)``: ``rounds`` counts every executed superstep including a
    final unproductive one (host-loop convention), ``stopped`` is True iff
    the loop exited on an unproductive round, ``final_active`` is the exit
    frontier size (0 and/or ``stopped`` ⇒ converged).
    """
    def cond(carry):
        _est, act, r, stop = carry[:4]
        return (~stop) & (r < max_rounds) & act.any()

    def body(carry):
        est, act, r, _stop, mb, cb, rb = carry
        new_est, changed, recv = _masked_round(est, src, dst, arc_mask,
                                               act, n, n_iters)
        any_ch = changed.any()
        mb = mb.at[r].set(jnp.sum(jnp.where(changed, deg, 0),
                                  dtype=jnp.int32))
        cb = cb.at[r].set(jnp.sum(changed, dtype=jnp.int32))
        rb = rb.at[r].set(jnp.sum(recv, dtype=jnp.int32))
        return new_est, recv, r + 1, ~any_ch, mb, cb, rb

    zeros = jnp.zeros(max_rounds, jnp.int32)
    carry = (est, active, jnp.int32(0), jnp.bool_(False),
             zeros, zeros, zeros)
    est, act, r, stop, mb, cb, rb = lax.while_loop(cond, body, carry)
    return est, r, stop, jnp.sum(act, dtype=jnp.int32), mb, cb, rb


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

    The masked shard_map superstep of ``_masked_sharded_superstep`` nested
    INSIDE the while_loop: the whole batch re-convergence is one shard_map
    program, with per-round cross-device traffic only (one est all_gather,
    one 1-bit changed all_gather, three scalar psums) — the host sees the
    final estimate plus the filled stat buffers, same contract and same
    exact accounting as ``fused_convergence``. Keyed on (mesh, axes, V,
    n_iters, max_rounds) like its per-round sibling so stable shard shapes
    reuse one compiled program across batches.

    Returns ``prog(est, src, dst, arc_mask, deg, active) -> (est', rounds,
    stopped, final_active, msgs_buf, changed_buf, recv_buf)`` with est'
    sharded like the state and everything else replicated.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distribution.compat import shard_map

    def prog(est, src, dst, arc_mask, deg, active):
        # shapes inside shard_map (per device): est (1, V), src (1, A), ...
        src_l, dst_l, am_l, deg_l = src[0], dst[0], arc_mask[0], deg[0]

        def cond(carry):
            _est, act, r, stop = carry[:4]
            return ((~stop) & (r < max_rounds)
                    & (lax.psum(jnp.sum(act, dtype=jnp.int32), axes) > 0))

        def body(carry):
            est_c, act_c, r, _stop, mb, cb, rb = carry
            est_glob = lax.all_gather(est_c, axes, axis=0,
                                      tiled=True).reshape(-1)
            est_dst = jnp.where(am_l, est_glob[dst_l], 0)
            h = _hindex_by_bsearch(est_c[0], est_dst, src_l, V, n_iters)
            new_l = jnp.where(act_c[0], h, est_c[0])
            changed_l = new_l < est_c[0]
            msgs = lax.psum(jnp.sum(jnp.where(changed_l, deg_l, 0),
                                    dtype=jnp.int32), axes)
            ch_cnt = lax.psum(jnp.sum(changed_l, dtype=jnp.int32), axes)
            ch_glob = lax.all_gather(changed_l[None], axes, axis=0,
                                     tiled=True).reshape(-1)
            recv_l = jax.ops.segment_sum(
                jnp.where(am_l, ch_glob[dst_l], False).astype(jnp.int32),
                src_l, num_segments=V) > 0
            rb = rb.at[r].set(lax.psum(jnp.sum(recv_l, dtype=jnp.int32),
                                       axes))
            return (new_l[None], recv_l[None], r + 1, ch_cnt == 0,
                    mb.at[r].set(msgs), cb.at[r].set(ch_cnt), rb)

        zeros = jnp.zeros(max_rounds, jnp.int32)
        carry = (est, active, jnp.int32(0), jnp.bool_(False),
                 zeros, zeros, zeros)
        est, act, r, stop, mb, cb, rb = lax.while_loop(cond, body, carry)
        final = lax.psum(jnp.sum(act, dtype=jnp.int32), axes)
        return est, r, stop, final, mb, cb, rb

    spec_state = P(axes)
    sharded = shard_map(prog, mesh=mesh, in_specs=(spec_state,) * 6,
                        out_specs=(spec_state,) + (P(),) * 6)
    return jax.jit(sharded)


# ---------------------------------------------------------------------- #
# Single-host rounds — ELL backend
# ---------------------------------------------------------------------- #

def hindex_rows_ref(nbr_est, est_u, n_iters):
    """Rowwise h-index of clip(nbr_est, 0, est_u) — jnp reference.

    nbr_est: (rows, w) int32 (sentinel slots hold 0), est_u: (rows,) int32.
    """
    vals = jnp.minimum(nbr_est, est_u[:, None])
    lo = jnp.zeros_like(est_u)
    hi = est_u

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi + 1) // 2
        cnt = jnp.sum(vals >= jnp.maximum(mid[:, None], 1), axis=1)
        ok = cnt >= mid
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    lo, hi = lax.fori_loop(0, n_iters, body, (lo, hi))
    return lo


def _make_round_ell(ell: EllGraph, n_iters: int, use_pallas: bool):
    if use_pallas:
        from repro.kernels.kcore_hindex.ops import hindex_rows as _hindex
    else:
        _hindex = hindex_rows_ref

    bucket_ids = [jnp.asarray(b.ids) for b in ell.buckets]
    bucket_nbrs = [jnp.asarray(b.nbrs) for b in ell.buckets]
    n = ell.n

    @jax.jit
    def round_ell(est_ext):
        """est_ext: (n+1,) int32, est_ext[n] == 0 (sentinel)."""
        new_ext = est_ext
        for ids, nbrs in zip(bucket_ids, bucket_nbrs):
            nbr_est = est_ext[nbrs]
            est_u = est_ext[ids]
            h = _hindex(nbr_est, est_u, n_iters)
            new_ext = new_ext.at[ids].set(h)
        new_ext = new_ext.at[n].set(0)          # keep sentinel pinned
        changed = new_ext[:n] < est_ext[:n]
        return new_ext, changed

    return round_ell


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
            new_u = _hindex_by_bsearch(est_u, est_dst, src[b], V, n_iters)
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
    (hypothesis-tested, BZ-verified). Fused is jacobi-only; the backend is
    ignored there (every backend computes the identical h-index, and the
    fused program always stages the segment arrays).
    """
    use_fused = config.fused if fused is None else fused
    if use_fused and config.mode != "jacobi":
        raise ValueError("fused=True requires mode='jacobi' "
                         f"(got {config.mode!r})")
    with _trace.layer("kcore.decompose", n=g.n, m=g.m, mode=config.mode,
                      backend=config.backend, fused=bool(use_fused)) as _sp:
        res = _decompose_body(g, config, use_fused)
        _sp.set(rounds=res.rounds, messages=res.stats.total_messages,
                converged=res.converged, recompiles=res.recompiles,
                compile_s=round(res.compile_s, 6), dispatch=res.dispatch)
    return res


def _decompose_body(g: Graph, config: KCoreConfig,
                    use_fused: bool) -> KCoreResult:
    compiles0, csecs0 = compile_count(), compile_seconds()
    phase_s: dict = {}
    dispatch_kind = "xla"
    devices: tuple = ()
    n = g.n
    if n == 0:
        return KCoreResult(core=np.zeros(0, np.int32), rounds=0,
                           converged=True,
                           stats=MessageStats(*(np.zeros(0, np.int64),) * 3))
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
            "fused" if use_fused else f"{config.mode}/{config.backend}",
            n=n)
        rec.record_round(active[0], msgs[0], changed_counts[0], est=g.deg)

    if use_fused:
        from repro.core.runtime import fused_converge_dense

        plan = _dispatch.resolve_plan(config.dispatch)
        ell = None
        with _trace.layer("stage", h2d_bytes=0) as ell_stage:
            if plan.kind == "pallas":
                from repro.graph.structs import build_ell

                # static fully-live adjacency + degree seed: the ELL
                # h-index route is exact here (see dispatch._round)
                ell = build_ell(g, widths=config.widths)
            arc_mask = np.ones(g.num_arcs, bool)
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
        dispatch_kind = outcome.dispatch
        devices = outcome.devices
        msgs.extend(outcome.msgs.tolist())
        changed_counts.extend(outcome.changed.tolist())
        active.extend(outcome.recv.tolist())
        core = outcome.est
        phase_s["device-converge"] = outcome.device_s
        phase_s["host-reconstruct"] = outcome.reconstruct_s
        phase_s["stage"] = ell_stage.seconds + outcome.stage_s
        phase_s["device-loop"] = outcome.loop_s

    elif config.backend == "segment" and config.mode == "jacobi":
        plan = _dispatch.resolve_plan(config.dispatch)
        dispatch_kind = plan.kind
        est = jnp.asarray(g.deg, jnp.int32)
        src = jnp.asarray(g.src, jnp.int32)
        dst = jnp.asarray(g.dst, jnp.int32)
        amask = jnp.ones(g.num_arcs, bool)
        if plan.kind == "pallas":
            from repro.graph.structs import build_ell

            ell = build_ell(g, widths=config.widths)
            prog = _dispatch.masked_round_program(
                n, n_iters, plan, g.src, g.dst, ell=ell)
            ones = jnp.ones(n, bool)

            def step(est):
                return prog(est, amask, ones)
        else:

            def step(est):
                return _round_segment(est, src, dst, amask, n, n_iters)
        rounds, converged = 0, False
        with _trace.layer("converge") as conv:
            while rounds < max_rounds:
                t_r = time.perf_counter() if rec.active else 0.0
                with _trace.span("kcore.round", round=rounds) as rsp:
                    new_est, changed, recv = step(est)
                    rounds += 1
                    ch_np = np.asarray(changed)
                    if not ch_np.any():
                        converged = True
                        break
                    msgs.append(int(deg64[ch_np].sum()))
                    changed_counts.append(int(ch_np.sum()))
                    active.append(int(np.asarray(recv).sum()))
                    rsp.set(messages=msgs[-1], changed=changed_counts[-1])
                    if rec.active:
                        rec.record_round(
                            active[rounds], msgs[-1], changed_counts[-1],
                            est=np.asarray(new_est), prev_est=np.asarray(est),
                            host_s=time.perf_counter() - t_r,
                            dispatch=dispatch_kind)
                    est = new_est
        phase_s["converge"] = conv.seconds
        core = np.asarray(est, np.int32)

    elif config.backend in ("ell", "ell_pallas") and config.mode == "jacobi":
        from repro.graph.structs import build_ell
        if config.backend == "ell_pallas":
            dispatch_kind = "pallas"
        ell = build_ell(g, widths=config.widths)
        round_fn = _make_round_ell(ell, n_iters,
                                   use_pallas=config.backend == "ell_pallas")
        est_ext = jnp.concatenate(
            [jnp.asarray(g.deg, jnp.int32), jnp.zeros(1, jnp.int32)])
        rounds, converged = 0, False
        with _trace.layer("converge") as conv:
            while rounds < max_rounds:
                t_r = time.perf_counter() if rec.active else 0.0
                with _trace.span("kcore.round", round=rounds):
                    new_ext, changed = round_fn(est_ext)
                    rounds += 1
                    ch_np = np.asarray(changed)
                    if not ch_np.any():
                        converged = True
                        break
                    msgs.append(int(deg64[ch_np].sum()))
                    changed_counts.append(int(ch_np.sum()))
                    # receivers: any vertex adjacent to a changed vertex
                    recv = _receivers_np(g, ch_np)
                    active.append(int(recv.sum()))
                    if rec.active:
                        rec.record_round(
                            active[rounds], msgs[-1], changed_counts[-1],
                            est=np.asarray(new_ext)[:n],
                            prev_est=np.asarray(est_ext)[:n],
                            host_s=time.perf_counter() - t_r,
                            dispatch=dispatch_kind)
                    est_ext = new_ext
        phase_s["converge"] = conv.seconds
        core = np.asarray(est_ext[:n], np.int32)

    elif config.mode == "block_gs":
        from repro.graph.partition import shard_graph
        sg = shard_graph(g, max(1, config.n_blocks))
        round_fn = _make_round_block_gs(sg, n_iters)
        est = jnp.asarray(sg.deg.reshape(-1), jnp.int32)
        rounds, converged = 0, False
        with _trace.layer("converge") as conv:
            while rounds < max_rounds:
                t_r = time.perf_counter() if rec.active else 0.0
                with _trace.span("kcore.round", round=rounds):
                    new_est, changed = round_fn(est)
                    rounds += 1
                    ch_real = np.asarray(changed)[: g.n]
                    if not ch_real.any():
                        converged = True
                        break
                    msgs.append(int(deg64[ch_real].sum()))
                    changed_counts.append(int(ch_real.sum()))
                    active.append(int(_receivers_np(g, ch_real).sum()))
                    if rec.active:
                        rec.record_round(
                            active[rounds], msgs[-1], changed_counts[-1],
                            est=np.asarray(new_est)[: g.n],
                            prev_est=np.asarray(est)[: g.n],
                            host_s=time.perf_counter() - t_r,
                            dispatch=dispatch_kind)
                    est = new_est
        phase_s["converge"] = conv.seconds
        core = np.asarray(est)[: g.n].astype(np.int32)

    else:
        raise ValueError(f"unsupported combo mode={config.mode} "
                         f"backend={config.backend}")

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
                       phase_s=phase_s, dispatch=dispatch_kind,
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

@functools.lru_cache(maxsize=128)
def _masked_sharded_superstep(mesh: jax.sharding.Mesh,
                              axes: tuple, V: int, n_iters: int):
    """Cached jitted frontier-masked sharded superstep (streaming path).

    Keyed on (mesh, axes, verts_per_shard, n_iters) so a churn stream whose
    shard shapes are stable (the engine pads them to powers of two) reuses
    one compiled program across batches. Same layout contract as
    ``make_sharded_superstep``; on top of the est all_gather a second 1-bit
    all_gather of the changed mask computes next round's receivers locally.

    Returns ``superstep(est, src, dst, arc_mask, deg, active) ->
    (est', changed, recv, msgs)`` with est'/changed/recv sharded like the
    state and msgs a replicated scalar.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distribution.compat import shard_map

    def superstep(est, src, dst, arc_mask, deg, active):
        est_l, act_l = est[0], active[0]
        est_glob = lax.all_gather(est, axes, axis=0, tiled=True).reshape(-1)
        est_dst = jnp.where(arc_mask[0], est_glob[dst[0]], 0)
        h = _hindex_by_bsearch(est_l, est_dst, src[0], V, n_iters)
        new_l = jnp.where(act_l, h, est_l)
        changed_l = new_l < est_l
        msgs = lax.psum(jnp.sum(jnp.where(changed_l, deg[0], 0)), axes)
        ch_glob = lax.all_gather(changed_l[None], axes, axis=0,
                                 tiled=True).reshape(-1)
        recv_l = jax.ops.segment_sum(
            jnp.where(arc_mask[0], ch_glob[dst[0]], False).astype(jnp.int32),
            src[0], num_segments=V) > 0
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
        new_l = _hindex_by_bsearch(est_l, est_dst, src[0], V, n_iters)
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
