"""Backend-aware superstep dispatch: XLA segment ops vs Pallas kernels.

Two Pallas kernels target the h-index superstep — ``kernels/kcore_hindex``
(rowwise clipped h-index over the degree-bucketed ELL layout) and
``kernels/segment_sum`` (blocked one-hot-matmul segment sum over sorted
COO). This module is the routing layer between them and the generic
``jax.ops.segment_sum`` programs of ``core.kcore``:

* ``resolve_plan()`` turns the platform dispatch switch
  (``repro.platform.dispatch_mode()`` — ``REPRO_PALLAS`` env var or a CLI
  flag) into a concrete ``DispatchPlan``: ``auto`` picks the Pallas kernels
  on TPU, where they compile natively, and the XLA segment ops on CPU;
  ``pallas`` forces the kernels (interpret mode on the CPU backend — exact,
  slow; the parity/CI path), ``xla`` keeps the XLA segment ops.
* ``masked_round_program`` / ``fused_convergence_program`` build jitted
  superstep programs with the SAME contract as
  ``core.kcore.masked_round_segment`` / ``core.kcore.fused_convergence``,
  but with the per-round reductions routed through the kernels: the
  binary-search hit counts and the receiver computation go through the
  blocked Pallas segment sum, and — when the caller provides the static
  degree-bucketed ``EllGraph`` — the whole per-vertex h-index goes through
  the Pallas ``hindex_rows`` kernel instead of the log2(maxdeg)
  segment-sum binary search.

Dispatch is an execution-placement choice, never an accounting one: cores
and per-round MessageStats are bit-equal across every (plan, mode) pair —
the kernels count 0/1 indicators exactly, ``ref.py`` stays the independent
oracle, and tests/test_dispatch.py asserts the equality across host, fused,
and sharded modes. The sharded (shard_map) paths keep the XLA segment ops.

The graph enters every program as jit ARGUMENTS (``GraphOperands``: arcs,
the blocked segment-sum layout, the ELL tables), never as closed-over
constants, so jax compiles one program per operand SHAPE: a graph of 10^8
arcs does not become an HLO literal, and a stream whose slot contents churn
at a stable padded size reuses one compiled program.
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
from repro.kernels.segment_sum.ops import blocked_layout, segment_sum_arrays


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
    """Device arrays a dispatched program reads (a pytree of jit args)."""

    src: jax.Array  # (A,) int32 arc sources
    dst: jax.Array  # (A,) int32 arc destinations
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


def _stage(plan: DispatchPlan, src, dst, n: int, ell: EllGraph | None):
    """Host graph arrays -> (GraphOperands, static layout args)."""
    src_np = np.asarray(src, np.int32)
    seg, R, n_rows_pad = (), 0, 0
    if plan.kind == "pallas":
        layout = blocked_layout(src_np, n)
        seg = tuple(jnp.asarray(a) for a in (layout.slot_edge, layout.rows_local, layout.block_row))
        R, n_rows_pad = layout.R, layout.n_rows_pad
    buckets = ()
    if ell is not None and plan.kind == "pallas":
        buckets = tuple((jnp.asarray(b.ids), jnp.asarray(b.nbrs)) for b in ell.buckets)
    ops = GraphOperands(jnp.asarray(src_np), jnp.asarray(dst, jnp.int32), seg, buckets)
    return ops, (("R", R), ("n_rows_pad", n_rows_pad))


# ---------------------------------------------------------------------- #
# Round body — the dispatched superstep
# ---------------------------------------------------------------------- #


def _round(ops: GraphOperands, est, arc_mask, active, *, n, n_iters, R, n_rows_pad):
    """Traceable masked superstep with dispatched reductions.

    Same math as ``core.kcore._masked_round``. With ELL tables (static
    fully-live adjacency only — the from-scratch decomposition) the h-index
    runs through the Pallas ``hindex_rows`` kernel per degree bucket;
    otherwise it is the binary search with the hit counts routed through
    the dispatched segment sum.
    """
    src, dst = ops.src, ops.dst

    if ops.seg:

        def count(mask):
            return segment_sum_arrays(mask, *ops.seg, R=R, n_rows_pad=n_rows_pad, n_rows=n)[:, 0]

    else:

        def count(mask):
            return jax.ops.segment_sum(mask.astype(jnp.int32), src, num_segments=n)

    if ops.ell:
        # est_ext[n] = 0: padded neighbor slots never count for k >= 1.
        # Requires est == 0 on degree-0 vertices (true from the degree
        # seed: they are in no bucket, so their estimate passes through)
        est_ext = jnp.concatenate([est, jnp.zeros(1, jnp.int32)])
        new_ext = est_ext
        for ids, nbrs in ops.ell:
            h = hindex_rows(est_ext[nbrs], est_ext[ids], n_iters=n_iters)
            new_ext = new_ext.at[ids].set(h)
        h = new_ext[:n]
    else:
        est_dst = jnp.where(arc_mask, est[dst], 0)

        def body(lohi, _):
            lo, hi = lohi
            mid = (lo + hi + 1) // 2
            cnt = count((est_dst >= mid[src]) & (mid[src] > 0))
            ok = cnt >= mid
            return (jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)), None

        # lax.scan (not fori_loop) like core.kcore._hindex_by_bsearch:
        # the trip count stays visible to jaxpr-walk cost analyses
        (h, _), _ = lax.scan(body, (jnp.zeros_like(est), est), None, length=n_iters)
    new_est = jnp.where(active, h, est)
    changed = new_est < est
    recv = count(jnp.where(arc_mask, changed[dst], False)) > 0
    return new_est, changed, recv


@functools.partial(jax.jit, static_argnames=("n", "n_iters", "R", "n_rows_pad"))
def _masked_round_jit(ops, est, arc_mask, active, *, n, n_iters, R, n_rows_pad):
    return _round(ops, est, arc_mask, active, n=n, n_iters=n_iters, R=R, n_rows_pad=n_rows_pad)


@functools.partial(jax.jit, static_argnames=("n", "n_iters", "max_rounds", "R", "n_rows_pad"))
def _fused_jit(ops, est, arc_mask, active, deg, *, n, n_iters, max_rounds, R, n_rows_pad):
    def cond(carry):
        _est, act, r, stop = carry[:4]
        return (~stop) & (r < max_rounds) & act.any()

    def body(carry):
        est, act, r, _stop, mb, cb, rb = carry
        new_est, changed, recv = _round(
            ops, est, arc_mask, act, n=n, n_iters=n_iters, R=R, n_rows_pad=n_rows_pad
        )
        any_ch = changed.any()
        mb = mb.at[r].set(jnp.sum(jnp.where(changed, deg, 0), dtype=jnp.int32))
        cb = cb.at[r].set(jnp.sum(changed, dtype=jnp.int32))
        rb = rb.at[r].set(jnp.sum(recv, dtype=jnp.int32))
        return new_est, recv, r + 1, ~any_ch, mb, cb, rb

    zeros = jnp.zeros(max_rounds, jnp.int32)
    carry = (est, active, jnp.int32(0), jnp.bool_(False), zeros, zeros, zeros)
    est, act, r, stop, mb, cb, rb = lax.while_loop(cond, body, carry)
    return est, r, stop, jnp.sum(act, dtype=jnp.int32), mb, cb, rb


def masked_round_program(
    n: int,
    n_iters: int,
    plan: DispatchPlan,
    src: np.ndarray,
    dst: np.ndarray,
    ell: EllGraph | None = None,
) -> Program:
    """Dispatched superstep: ``(est, arc_mask, active) -> (new_est,
    changed, recv)`` — ``core.kcore.masked_round_segment`` with the
    reductions routed per ``plan``.
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
    """Dispatched fused convergence loop.

    Same carry, cond, stat buffers, and output contract as
    ``core.kcore.fused_convergence`` — ``prog(est, arc_mask, active, deg)
    -> (est', rounds, stopped, final_active, msgs_buf, changed_buf,
    recv_buf)`` — with the while_loop BODY routed through the Pallas
    kernels per ``plan``. Accounting is reconstructed by the shared
    ``fused_round_stats``, so the bill is bit-equal to every other mode.
    """
    ops, layout = _stage(plan, src, dst, n, ell)
    static = (("n", n), ("n_iters", n_iters), ("max_rounds", max_rounds)) + layout
    return Program(_fused_jit, ops, static)
