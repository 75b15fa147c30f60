"""Out-of-core block-cycling k-core decomposition — bounded device memory.

The in-memory modes (host loop, fused while_loop, sharded) materialize the
FULL arc arrays on device, so the largest decomposable graph is capped by
device memory. This driver removes that cap by cycling
``repro.graph.blockstore`` blocks through the device one at a time, exactly
as Gao et al. cycle disk blocks through a small compute tier (PAPERS.md):

  * vertex-indexed state (estimates, frontier, degrees) stays dense on the
    host — O(n) int32/bool, two orders of magnitude below the arc arrays;
  * per round, each block with ≥1 active vertex is materialized (through a
    byte-budgeted LRU ``BlockCache``) and runs ONE masked Jacobi superstep
    on device: the same ``hindex_by_bsearch`` as every other mode,
    over the block's (V,) vertices and (A,) arcs only;
  * the *halo buffer* is the per-block gather ``est_prev[dst]`` — the
    neighbor estimates a block needs, shipped as one (A,) vector instead of
    the whole estimate array;
  * blocks whose vertex range has NO active vertices are skipped without
    loading — the frontier masks the engines already maintain double as a
    block-level I/O filter, so the load rate collapses with the frontier.

Exactness: every block superstep reads the ROUND-START estimates
(``est_prev``), so a full sweep is one synchronous Jacobi round — the same
operator the host loop and the fused while_loop iterate. Cores AND the
per-round message bill are therefore bit-equal to every in-memory mode
(BZ-oracle-verified, asserted in tests/test_outofcore.py). Receivers are
accumulated from the *loaded* blocks only: ``recv[dst] |= changed[src]``
over each processed block's arcs equals the host loop's
``segment_sum(changed[dst])`` because the arc set is symmetric (both
directions of every undirected edge are stored, dead slots die in pairs)
and a vertex can only change inside a processed block.
"""

from __future__ import annotations

import dataclasses
import functools
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dispatch import hindex_by_bsearch
from repro.core.jit_telemetry import compile_count, compile_seconds
from repro.core.kcore import KCoreResult, _bs_iters
from repro.core.messages import MessageStats
from repro.graph.blockstore import (ARC_SLOT_BYTES, BlockCache, BlockStore,
                                    plan_blocks)
from repro.graph.structs import Graph
from repro.obs import flight as _flight
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace


def peak_rss_bytes() -> int:
    """Process peak resident set size (ru_maxrss is KiB on Linux)."""
    import resource
    import sys

    ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return int(ru) if sys.platform == "darwin" else int(ru) * 1024


@dataclasses.dataclass
class OutOfCoreStats:
    """Block-cycling telemetry for one decomposition."""

    n_blocks: int
    V: int
    A: int
    rounds: int
    blocks_loaded: int  # cache misses — blocks actually read from disk
    blocks_skipped: int  # block-rounds skipped via the frontier mask
    block_rounds: int  # block supersteps executed (loads + cache hits)
    cache_hits: int
    evictions: int
    cache_peak_bytes: int
    mem_budget: int | None
    device_block_bytes: int  # largest block shipped, in arc bytes (device peak)
    total_arc_bytes: int  # full arc arrays (the in-memory footprint)
    imbalance: float  # max/mean live arcs per block (straggler factor)
    peak_rss_bytes: int
    ms_per_round: float

    @property
    def skip_rate(self) -> float:
        total = self.block_rounds + self.blocks_skipped
        return self.blocks_skipped / max(total, 1)

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["skip_rate"] = round(self.skip_rate, 4)
        return d


@dataclasses.dataclass
class OutOfCoreResult(KCoreResult):
    """KCoreResult plus the block-cycling telemetry."""

    block_stats: OutOfCoreStats | None = None


def row_offset_hits(est_dst, src_local, row_off):
    """``count_hits`` (``dispatch.hindex_by_bsearch``) over a block's
    src-sorted arcs: a cumsum of the hits, differenced at the rows'
    offsets — an exact integer rewrite of the segment sum that sidesteps
    XLA's serialized scatter-add on CPU (~8x per superstep)."""

    def count_hits(mid):
        hit = (est_dst >= mid[src_local]) & (mid[src_local] > 0)
        c = jnp.concatenate([jnp.zeros(1, jnp.int32),
                             jnp.cumsum(hit.astype(jnp.int32))])
        return c[row_off[1:]] - c[row_off[:-1]]

    return count_hits


@functools.partial(jax.jit, static_argnames=("n_iters",))
def _block_superstep(est_u, est_dst_masked, src_local, row_off, active,
                     n_iters):
    """One masked Jacobi superstep over a single resident block.

    The in-memory round (``dispatch._round``) restricted to the block: the
    caller pre-gathers the halo ``est_dst_masked = where(mask, est_prev[dst],
    0)`` on the host, so the device only ever sees (V,) vertex state and
    (A,) arc state, and the binary search counts hits by
    ``row_offset_hits``. Arc inputs arrive sliced to the block's pow2
    LENGTH BUCKET (not the store-wide max A), so the straggler block no
    longer inflates every other block's arc slots; the bucket count bounds
    the number of compiled shapes at ~log2(A).
    """
    h = hindex_by_bsearch(
        est_u, row_offset_hits(est_dst_masked, src_local, row_off), n_iters)
    new = jnp.where(active, h, est_u)
    return new, new < est_u


def _bucket(length: int, cap: int) -> int:
    """Smallest pow2 >= ``length`` (min 8), clamped to the store-wide A."""
    b = 8
    while b < length:
        b <<= 1
    return min(b, cap)


def _publish_metrics(stats: OutOfCoreStats) -> None:
    """Fold the block-cycling telemetry into the process metrics registry."""
    _metrics.counter("kcore_ooc_blocks_loaded_total").inc(stats.blocks_loaded)
    _metrics.counter("kcore_ooc_blocks_skipped_total").inc(
        stats.blocks_skipped)
    _metrics.counter("kcore_ooc_evictions_total").inc(stats.evictions)
    _metrics.gauge("kcore_ooc_device_block_bytes").set(
        stats.device_block_bytes)
    _metrics.gauge("kcore_ooc_total_arc_bytes").set(stats.total_arc_bytes)
    _metrics.gauge("kcore_ooc_cache_peak_bytes").set(stats.cache_peak_bytes)
    _metrics.gauge("kcore_ooc_peak_rss_bytes").set(stats.peak_rss_bytes)
    _metrics.gauge("kcore_block_imbalance").set(stats.imbalance)


def outofcore_decompose(source, *, mem_budget: int | None = None,
                        n_blocks: int | None = None,
                        max_rounds: int | None = None,
                        store_dir: str | None = None,
                        deg: np.ndarray | None = None,
                        keep_store: bool = False) -> OutOfCoreResult:
    """Decompose to the exact fixpoint while keeping ≤ one block on device.

    ``source`` is a ``Graph`` (a temporary ``BlockStore`` is written under
    ``store_dir`` / the system tmpdir and deleted afterwards unless
    ``keep_store``), an opened ``BlockStore``, or a store directory path.
    ``mem_budget`` bounds the LRU block cache in bytes — ``plan_blocks``
    picks the block count from it when ``n_blocks`` is not forced.
    ``deg`` must be passed (full (n,) int32) when ``source`` is a store
    built from masked arrays whose degrees are not ``mask``-weighted
    bincounts of the stored arcs; for stores written from a ``Graph`` it is
    reconstructed from the blocks on a single streaming pass.

    The accounting contract matches every in-memory mode bit for bit:
    round 0 bills the degree broadcast (2m messages, n senders, all-vertex
    frontier), round r ≥ 1 bills Σ deg over vertices whose estimate
    dropped, and the active series is the receiver counts.
    """
    tmp = None
    if isinstance(source, Graph):
        g: Graph = source
        if n_blocks is None:
            n_blocks = plan_blocks(g.n, g.src, mem_budget)
        tmp = tempfile.mkdtemp(prefix="kcore_blocks_", dir=store_dir)
        store = BlockStore.create(f"{tmp}/store", g, n_blocks=n_blocks)
        deg = g.deg
    elif isinstance(source, BlockStore):
        store = source
    else:
        store = BlockStore.open(source)
    try:
        return _decompose_store(store, deg=deg, mem_budget=mem_budget,
                                max_rounds=max_rounds)
    finally:
        if tmp is not None and not keep_store:
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)


def _store_degrees(store: BlockStore) -> np.ndarray:
    """(n_pad,) mask-weighted degrees via one streaming pass over blocks."""
    deg = np.zeros(store.n_pad, np.int32)
    for b in range(store.n_blocks):
        raw_src, _raw_dst, raw_mask = store.block_raw(b)
        if raw_src.shape[0]:
            deg[b * store.V:(b + 1) * store.V] += np.bincount(
                np.asarray(raw_src)[np.asarray(raw_mask)],
                minlength=store.V).astype(np.int32)
    return deg


def _decompose_store(store: BlockStore, *, deg: np.ndarray | None,
                     mem_budget: int | None,
                     max_rounds: int | None) -> OutOfCoreResult:
    compiles0, csecs0 = compile_count(), compile_seconds()
    n, V, n_blocks = store.n, store.V, store.n_blocks
    n_pad = store.n_pad
    if n == 0:
        zero = MessageStats(*(np.zeros(0, np.int64),) * 3)
        return OutOfCoreResult(core=np.zeros(0, np.int32), rounds=0,
                               converged=True, stats=zero)

    # dense host vertex state — the out-of-core tier's only O(n) arrays
    if deg is None:
        deg_pad = _store_degrees(store)
    else:
        deg_pad = np.zeros(n_pad, np.int32)
        deg_pad[:n] = np.asarray(deg, np.int32)
    deg64 = deg_pad[:n].astype(np.int64)
    est = deg_pad.copy()
    # round-1 frontier: vertices that received the degree broadcast. Using
    # it as the compute mask too is exact (deg-0 vertices hold est 0, a
    # fixpoint) and lets round 1 already skip all-isolated blocks.
    active_mask = np.zeros(n_pad, bool)
    active_mask[:n] = deg_pad[:n] > 0
    n_iters = _bs_iters(int(deg_pad.max()) if n_pad else 0)
    cap = max_rounds if max_rounds is not None else n + 1

    msgs = [int(deg64.sum())]  # round 0: degree broadcast = 2m
    active = [n, int((deg64 > 0).sum())]
    changed_counts = [n]

    cache = BlockCache(store, budget_bytes=mem_budget)
    skipped = block_rounds = 0
    rounds, converged = 0, False
    # per-block device geometry: each block ships only its pow2 LENGTH
    # BUCKET of arc slots (tail padding beyond its real run is dropped),
    # so the straggler block's A doesn't inflate every superstep. row_off
    # is the block's CSR row index over those slots (cached; O(n_pad) ints
    # total — vertex-tier host state).
    a_eff = {b: _bucket(int(store.arcs_per_block[b]), store.A)
             for b in range(n_blocks)}
    row_offs: dict[int, np.ndarray] = {}
    dev_bytes_peak = 0

    rec = _flight.recorder()
    if rec.active:
        rec.start_run("static", "out_of_core", n=n)
        rec.record_round(active[0], msgs[0], changed_counts[0],
                         est=deg_pad[:n])

    with _trace.span("kcore.decompose", n=n, m=int(deg64.sum()) // 2,
                     mode="out_of_core", n_blocks=n_blocks,
                     mem_budget=mem_budget or 0) as _sp:
        t_conv = time.perf_counter()
        while rounds < cap:
            t_r = time.perf_counter() if rec.active else 0.0
            with _trace.span("kcore.round", round=rounds) as rsp:
                est_prev = est.copy()
                changed = np.zeros(n_pad, bool)
                recv = np.zeros(n_pad, bool)
                blocks_hit = 0
                for b in range(n_blocks):
                    lo = b * V
                    if not active_mask[lo:lo + V].any():
                        skipped += 1
                        continue
                    blocks_hit += 1
                    block_rounds += 1
                    blk = cache.get(b)
                    ae = a_eff[b]
                    dev_bytes_peak = max(dev_bytes_peak,
                                         ae * ARC_SLOT_BYTES)
                    src_e, dst_e = blk.src[:ae], blk.dst[:ae]
                    mask_e = blk.mask[:ae]
                    if b not in row_offs:
                        row_offs[b] = np.minimum(
                            np.searchsorted(src_e, np.arange(V + 1)),
                            ae).astype(np.int32)
                    # halo: this block's neighbor estimates, gathered from
                    # the ROUND-START vector (synchronous Jacobi — the
                    # bit-equality contract with every in-memory mode)
                    halo = np.where(mask_e, est_prev[dst_e], 0)
                    new_u, ch_u = _block_superstep(
                        jnp.asarray(est_prev[lo:lo + V]),
                        jnp.asarray(halo.astype(np.int32)),
                        jnp.asarray(src_e),
                        jnp.asarray(row_offs[b]),
                        jnp.asarray(active_mask[lo:lo + V]),
                        n_iters=n_iters)
                    ch_u = np.asarray(ch_u)
                    est[lo:lo + V] = np.asarray(new_u)
                    changed[lo:lo + V] = ch_u
                    # receiver scatter: arcs whose (local) src changed mark
                    # their dst — equals the pull-side segment_sum because
                    # the arc set is symmetric
                    sel = mask_e & ch_u[src_e]
                    if sel.any():
                        recv[dst_e[sel]] = True
                rounds += 1
                if not changed.any():
                    converged = True
                    rsp.set(blocks=blocks_hit, converged=True)
                    break
                msgs.append(int(deg64[changed[:n]].sum()))
                changed_counts.append(int(changed.sum()))
                active.append(int(recv.sum()))
                rsp.set(messages=msgs[-1], changed=changed_counts[-1],
                        blocks=blocks_hit)
                if rec.active:
                    rec.record_round(
                        active[rounds], msgs[-1], changed_counts[-1],
                        est=est[:n], prev_est=est_prev[:n],
                        host_s=time.perf_counter() - t_r)
                active_mask = recv
        wall = time.perf_counter() - t_conv
        _sp.set(rounds=rounds, converged=converged,
                blocks_loaded=cache.loads, blocks_skipped=skipped,
                evictions=cache.evictions)

    stats = MessageStats(
        messages_per_round=np.asarray(msgs, np.int64),
        active_per_round=np.asarray(active[: len(msgs)], np.int64),
        changed_per_round=np.asarray(changed_counts[: len(msgs)], np.int64),
    )
    block_stats = OutOfCoreStats(
        n_blocks=n_blocks, V=V, A=store.A, rounds=rounds,
        blocks_loaded=cache.loads, blocks_skipped=skipped,
        block_rounds=block_rounds, cache_hits=cache.hits,
        evictions=cache.evictions, cache_peak_bytes=cache.peak_bytes,
        mem_budget=mem_budget,
        device_block_bytes=dev_bytes_peak or store.block_arc_bytes,
        total_arc_bytes=store.total_arc_bytes,
        imbalance=store.balance()["imbalance"],
        peak_rss_bytes=peak_rss_bytes(),
        ms_per_round=1e3 * wall / max(rounds, 1),
    )
    _publish_metrics(block_stats)
    if rec.active:
        rec.end_run(converged=converged, messages=int(stats.total_messages),
                    blocks_loaded=block_stats.blocks_loaded,
                    blocks_skipped=block_stats.blocks_skipped,
                    device_block_bytes=block_stats.device_block_bytes,
                    peak_rss_bytes=block_stats.peak_rss_bytes)
    return OutOfCoreResult(
        core=est[:n].astype(np.int32), rounds=rounds, converged=converged,
        stats=stats, recompiles=compile_count() - compiles0,
        compile_s=compile_seconds() - csecs0,
        phase_s={"converge": wall}, block_stats=block_stats)
