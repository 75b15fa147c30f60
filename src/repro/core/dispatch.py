"""The k-core superstep: one round body, one binary search, one fused loop.

Every single-device round runs through ``_round``; the host round loops
step it through ``masked_round_program`` and the fused runtime iterates it
inside ``converge_loop``, the one device-resident ``lax.while_loop`` with
per-round stat buffers (the shard_map loop of ``core.kcore`` shares it, with
its sums reduced over the mesh). Every binary-search h-index in the package
is ``hindex_by_bsearch``; callers differ only in how they count a probe's
hits.

Pallas kernels target the h-index superstep — ``kernels/kcore_hindex``
(rowwise clipped h-index over the degree-bucketed ELL layout) and
``kernels/segment_sum`` (blocked one-hot-matmul segment sum over sorted
COO, and the binary search's per-row hit count ``row_hits`` on the same
layout). The plan decides which reductions the round routes through them:

* ``resolve_plan()`` turns the platform dispatch switch
  (``repro.platform.dispatch_mode()`` — ``REPRO_PALLAS`` env var or a CLI
  flag) into a concrete ``DispatchPlan``: ``auto`` picks the Pallas kernels
  on TPU, where they compile natively, and the XLA segment ops on CPU;
  ``pallas`` forces the kernels (interpret mode on the CPU backend — exact,
  slow; the parity/CI path), ``xla`` keeps the XLA segment ops.
* On the ``xla`` plan the operands are the arcs in arc order and both
  counts are ``jax.ops.segment_sum``. On the ``pallas`` plan the
  binary-search hit counts go through ``row_hits``, the receiver
  computation through the blocked Pallas segment sum, and — when the
  caller provides the static degree-bucketed ``EllGraph`` — the whole
  per-vertex h-index goes through the Pallas ``hindex_rows`` kernel
  instead of the log2(maxdeg) binary search.

Dispatch is an execution-placement choice, never an accounting one: cores
and per-round MessageStats are bit-equal across every (plan, mode) pair —
the kernels count 0/1 indicators exactly, ``ref.py`` stays the independent
oracle, and tests/test_dispatch.py asserts the equality across host, fused,
and streaming modes. The sharded (shard_map) paths keep the XLA segment ops.

Inside the programs the parts of the round carry ``jax.named_scope``s —
``kcore.gather``, ``kcore.hindex``, ``kcore.changed``, ``kcore.recv`` and
(in the fused loop) ``kcore.stats`` — and the kernels their
``pallas_call`` names (``kcore_hindex``, ``segment_sum``, ``row_hits``),
so a device trace ties each op to the part of the round it computes.
Staging the graph operands is a ``stage`` layer span
(repro.obs.trace.layer) that counts the bytes it copies to the device
(``h2d_bytes``).

The graph enters every program as jit ARGUMENTS (``GraphOperands``: arcs,
the blocked segment-sum layout, the ELL tables), never as closed-over
constants, so jax compiles one program per operand SHAPE: a graph of 10^8
arcs does not become an HLO literal, and a stream whose slot contents churn
at a stable padded size reuses one compiled program. With the blocked
layout the arc operands are staged in its slot order, and the arc mask is
permuted into it once per call: every mask the round counts is then built
from vertex-sized vectors where the kernel reads it, with no arc-sized
gather per count.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import platform as _platform
from repro.graph.structs import EllGraph
from repro.kernels.kcore_hindex.ops import hindex_rows
from repro.kernels.segment_sum.ops import (
    blocked_layout,
    row_hits_arrays,
    segment_sum_arrays,
    slot_rows,
    to_slots,
)
from repro.obs import trace


@dataclasses.dataclass(frozen=True)
class DispatchPlan:
    """Resolved kernel-dispatch decision for superstep programs.

    ``kind`` is ``"xla"`` (generic segment ops) or ``"pallas"`` (route
    through the kernels package); ``interpret`` records whether Pallas
    kernels run interpreted (the CPU backend only) — informational for
    reports, the kernels' ops wrappers decide it themselves.
    """

    kind: str = "xla"
    interpret: bool = True


def resolve_plan(mode: str | None = None) -> DispatchPlan:
    """Resolve auto/pallas/xla (default: the platform layer's switch)."""
    mode = _platform.normalize_dispatch(mode) if mode else "auto"
    if mode == "auto":
        # "auto" (incl. the KCoreConfig default) defers to the platform
        # switch, so REPRO_PALLAS / --dispatch reach every call site that
        # didn't pin a mode explicitly
        mode = _platform.dispatch_mode()
    interpret = _platform.interpret_kernels()
    if mode == "auto":
        mode = "xla" if interpret else "pallas"
    return DispatchPlan(kind=mode, interpret=interpret)


# ---------------------------------------------------------------------- #
# Graph operands — staged once per graph, passed to the jitted programs
# ---------------------------------------------------------------------- #


class GraphOperands(NamedTuple):
    """Device arrays a dispatched program reads (a pytree of jit args).

    On the XLA plan ``src``/``dst`` are the arcs in arc order. With a
    blocked layout (``seg``, the Pallas plan) they are in the layout's
    padded slot order, so every mask the round counts is built in the order
    the segment-sum kernel reads it: the slot's own row, and the arc's
    destination, which is the sentinel vertex ``n`` on padding slots.
    """

    src: jax.Array  # (A,) int32 arc sources; (E_pad,) slot rows with a layout
    dst: jax.Array  # (A,) int32 arc destinations; (E_pad,) with n on padding
    seg: tuple  # (slot_edge, rows_local, block_row) blocked layout; () on xla
    ell: tuple  # ((ids, nbrs), ...) per ELL bucket; () for the bsearch h-index


@dataclasses.dataclass(frozen=True)
class Program:
    """A jitted superstep program with its graph operands bound.

    ``fn`` is a module-level ``jax.jit`` function, so its compiled programs
    are cached by jax on the operand shapes and the ``static`` values.
    """

    fn: Callable
    operands: GraphOperands
    static: tuple  # (name, value) pairs of static arguments

    def __call__(self, *args):
        return self.fn(self.operands, *args, **dict(self.static))

    def lower(self, *args):
        return self.fn.lower(self.operands, *args, **dict(self.static))


def to_device(stage: trace.LayerSpan, x, dtype=None) -> jax.Array:
    """``jnp.asarray(x, dtype)``, adding to ``stage``'s ``h2d_bytes`` the
    bytes copied when ``x`` is a host array (0 when it is on the device)."""
    out = jnp.asarray(x, dtype)
    if not isinstance(x, jax.Array):
        stage.add("h2d_bytes", out.nbytes)
    return out


def _stage(plan: DispatchPlan, src, dst, n: int, ell: EllGraph | None):
    """Graph arrays -> (GraphOperands, static layout args), inside one
    ``stage`` layer span. The Pallas plan builds the blocked layout (and
    takes the ELL tables) on the host; the XLA plan copies the arcs as
    they are, and arcs already on the device cost nothing."""
    # staging makes arc-sized temporaries on every call: keep them in the
    # heap, or some calls fault every page in afresh
    _platform.pin_host_heap()
    with trace.layer("stage", h2d_bytes=0) as st:
        seg, buckets, R, n_rows_pad = (), (), 0, 0
        if plan.kind == "pallas":
            src, dst = np.asarray(src, np.int32), np.asarray(dst, np.int32)
            layout = blocked_layout(src, n)
            seg = tuple(to_device(st, a) for a in (layout.slot_edge, layout.rows_local, layout.block_row))
            R, n_rows_pad = layout.R, layout.n_rows_pad
            src, dst = slot_rows(layout), to_slots(dst, layout.slot_edge, n)
            if ell is not None:
                buckets = tuple((to_device(st, b.ids), to_device(st, b.nbrs)) for b in ell.buckets)
        ops = GraphOperands(to_device(st, src, jnp.int32), to_device(st, dst, jnp.int32), seg, buckets)
    return ops, (("R", R), ("n_rows_pad", n_rows_pad))


# ---------------------------------------------------------------------- #
# The binary-search h-index
# ---------------------------------------------------------------------- #


def hindex_by_bsearch(est, count_hits, n_iters: int):
    """Per-vertex h-index by binary search: for every vertex u the largest
    k in [0, est[u]] with ``count_hits(k)[u] >= k``.

    ``count_hits(mid)`` takes one probe per vertex and returns, per vertex,
    how many of its arcs have a neighbour estimate >= its probe, counting
    nothing for a probe of 0; callers differ only in how they count
    (``arc_hits``, the ``row_hits`` kernel, a block's cumsum). ``n_iters``
    steps must cover ``est``: ``core.kcore._bs_iters(max estimate)``.
    """

    def body(lohi, _):
        lo, hi = lohi
        mid = (lo + hi + 1) // 2
        ok = count_hits(mid) >= mid
        return (jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)), None

    # lax.scan (not fori_loop): the trip count stays visible to jaxpr-walk
    # cost analyses
    (h, _), _ = lax.scan(body, (jnp.zeros_like(est), est), None, length=n_iters)
    return h


def arc_hits(est_dst, src, n: int):
    """``count_hits`` over arcs in any order: the XLA segment sum, by
    source, of ``est_dst >= mid[src] > 0``. ``est_dst`` holds each arc's
    neighbour estimate, 0 on masked arcs (they never count)."""

    def count_hits(mid):
        hit = (est_dst >= mid[src]) & (mid[src] > 0)
        return jax.ops.segment_sum(hit.astype(jnp.int32), src, num_segments=n)

    return count_hits


# ---------------------------------------------------------------------- #
# Round body — the dispatched superstep
# ---------------------------------------------------------------------- #


def _operand_mask(ops: GraphOperands, arc_mask):
    """``arc_mask`` in the order of ``ops.src``/``ops.dst``: as given on the
    XLA plan; permuted into the blocked layout's slot order (False on
    padding slots) with a layout. A program does this once per call,
    outside its round loop."""
    if not ops.seg:
        return arc_mask
    with jax.named_scope("kcore.mask"):
        return to_slots(arc_mask, ops.seg[0], False)


def _round(ops: GraphOperands, est, mask, active, *, n, n_iters, R, n_rows_pad):
    """Traceable masked superstep with dispatched reductions; ``mask`` is
    the arc mask in the operands' order (``_operand_mask``). Returns
    ``(new_est, changed, recv)``: only ``active`` vertices recompute, and
    ``recv`` marks the vertices with an arc to a changed one.

    With ELL tables (static fully-live adjacency only — the from-scratch
    decomposition) the h-index runs through the Pallas ``hindex_rows``
    kernel per degree bucket; otherwise it is the binary search, whose hit
    counts go through the ``row_hits`` kernel with a blocked layout (each
    row's probe reaches its slots inside the kernel) and through the XLA
    segment sum without one. Every counted mask is built in the operands'
    order from vertex-sized vectors, so with a blocked layout it reaches
    the kernel with no permutation.
    """
    src, dst = ops.src, ops.dst

    if ops.seg:

        def count(m):
            return segment_sum_arrays(m, *ops.seg, R=R, n_rows_pad=n_rows_pad, n_rows=n)[:, 0]

    else:

        def count(m):
            return jax.ops.segment_sum(m.astype(jnp.int32), src, num_segments=n)

    # est_ext[n] = 0: the padding slots' sentinel destination, and the ELL
    # tables' padded neighbor slots, never count for k >= 1
    est_ext = jnp.concatenate([est, jnp.zeros(1, jnp.int32)])
    if ops.ell:
        # Requires est == 0 on degree-0 vertices (true from the degree
        # seed: they are in no bucket, so their estimate passes through)
        new_ext = est_ext
        for ids, nbrs in ops.ell:
            with jax.named_scope("kcore.gather"):
                nbr_est, own_est = est_ext[nbrs], est_ext[ids]
            with jax.named_scope("kcore.hindex"):
                h = hindex_rows(nbr_est, own_est, n_iters=n_iters)
                new_ext = new_ext.at[ids].set(h)
        h = new_ext[:n]
    else:
        with jax.named_scope("kcore.gather"):
            est_dst = jnp.where(mask, est_ext[dst], 0)
        if ops.seg:

            def count_hits(mid):
                # each row's probe reaches its slots inside the kernel
                return row_hits_arrays(est_dst, mid, *ops.seg[1:], R=R, n_rows_pad=n_rows_pad, n_rows=n)

        else:
            count_hits = arc_hits(est_dst, src, n)
        with jax.named_scope("kcore.hindex"):
            h = hindex_by_bsearch(est, count_hits, n_iters)
    with jax.named_scope("kcore.changed"):
        new_est = jnp.where(active, h, est)
        changed = new_est < est
    with jax.named_scope("kcore.recv"):
        changed_ext = jnp.concatenate([changed, jnp.zeros(1, jnp.bool_)])
        recv = count(changed_ext[dst] & mask) > 0
    return new_est, changed, recv


def row_hit_steps(ops: GraphOperands, n_iters: int) -> int:
    """``row_hits`` kernel calls one ``_round`` on ``ops`` makes: one per
    binary-search step on the slot route, none with ELL tables or on the
    XLA plan."""
    return n_iters if ops.seg and not ops.ell else 0


@functools.partial(jax.jit, static_argnames=("n", "n_iters", "R", "n_rows_pad"))
def _masked_round_jit(ops, est, arc_mask, active, *, n, n_iters, R, n_rows_pad):
    mask = _operand_mask(ops, arc_mask)
    return _round(ops, est, mask, active, n=n, n_iters=n_iters, R=R, n_rows_pad=n_rows_pad)


def converge_loop(step, est, active, deg, *, max_rounds, any_=jnp.any, total=lambda x: x):
    """Iterate ``step(est, active) -> (new_est, changed, recv)`` to the
    fixpoint in ONE ``lax.while_loop``: the round after a round ``r`` runs
    on ``r``'s receivers, and the loop stops on an unproductive round, an
    empty frontier, or ``max_rounds``.

    Per executed round ``r`` the body fills three ``(max_rounds,)`` int32
    buffers — messages (the degrees of the changed vertices; < 2m < 2^31
    per round for every graph we target, accumulated to int64 on the
    host), changed count, and receiver count — from which
    ``core.kcore.fused_round_stats`` reconstructs per-round
    ``MessageStats`` exactly equal to the host loops' accounting.

    ``any_`` and ``total`` reduce a vector's any and an int32 sum across
    the program: as they are on one device; over the mesh axes inside
    shard_map (``core.kcore._fused_sharded_convergence``).

    Returns ``(est', rounds, stopped, final_active, msgs_buf, changed_buf,
    recv_buf)``: ``rounds`` counts every executed superstep including a
    final unproductive one (host-loop convention), ``stopped`` is True iff
    the loop exited on an unproductive round, ``final_active`` is the exit
    frontier size (0 and/or ``stopped`` ⇒ converged).
    """

    def cond(carry):
        _est, act, r, stop = carry[:4]
        return (~stop) & (r < max_rounds) & any_(act)

    def body(carry):
        est, act, r, _stop, mb, cb, rb = carry
        new_est, changed, recv = step(est, act)
        with jax.named_scope("kcore.stats"):
            any_ch = any_(changed)
            mb = mb.at[r].set(total(jnp.sum(jnp.where(changed, deg, 0), dtype=jnp.int32)))
            cb = cb.at[r].set(total(jnp.sum(changed, dtype=jnp.int32)))
            rb = rb.at[r].set(total(jnp.sum(recv, dtype=jnp.int32)))
        return new_est, recv, r + 1, ~any_ch, mb, cb, rb

    zeros = jnp.zeros(max_rounds, jnp.int32)
    carry = (est, active, jnp.int32(0), jnp.bool_(False), zeros, zeros, zeros)
    est, act, r, stop, mb, cb, rb = lax.while_loop(cond, body, carry)
    return est, r, stop, total(jnp.sum(act, dtype=jnp.int32)), mb, cb, rb


@functools.partial(jax.jit, static_argnames=("n", "n_iters", "max_rounds", "R", "n_rows_pad"))
def _fused_jit(ops, est, arc_mask, active, deg, *, n, n_iters, max_rounds, R, n_rows_pad):
    mask = _operand_mask(ops, arc_mask)

    def step(est, act):
        return _round(ops, est, mask, act, n=n, n_iters=n_iters, R=R, n_rows_pad=n_rows_pad)

    return converge_loop(step, est, active, deg, max_rounds=max_rounds)


def masked_round_program(
    n: int,
    n_iters: int,
    plan: DispatchPlan,
    src: np.ndarray,
    dst: np.ndarray,
    ell: EllGraph | None = None,
) -> Program:
    """One frontier-masked superstep: ``(est, arc_mask, active) ->
    (new_est, changed, recv)`` with the reductions routed per ``plan``.

    Only vertices with ``active`` True recompute their h-index; everyone
    else keeps their estimate. With ``active`` all-True this is the paper's
    plain synchronous superstep; after an edge-churn batch only the
    frontier recomputes, which is exact for the monotone locality operator
    (an inactive vertex's inputs are unchanged).
    """
    ops, layout = _stage(plan, src, dst, n, ell)
    return Program(_masked_round_jit, ops, (("n", n), ("n_iters", n_iters)) + layout)


def fused_convergence_program(
    n: int,
    n_iters: int,
    max_rounds: int,
    plan: DispatchPlan,
    src: np.ndarray,
    dst: np.ndarray,
    ell: EllGraph | None = None,
) -> Program:
    """The fused convergence loop (``converge_loop`` over ``_round``):
    ``prog(est, arc_mask, active, deg) -> (est', rounds, stopped,
    final_active, msgs_buf, changed_buf, recv_buf)``, the reductions routed
    per ``plan``. Accounting is reconstructed by ``fused_round_stats``, so
    the bill is bit-equal to every host-loop mode.
    """
    ops, layout = _stage(plan, src, dst, n, ell)
    static = (("n", n), ("n_iters", n_iters), ("max_rounds", max_rounds)) + layout
    return Program(_fused_jit, ops, static)
