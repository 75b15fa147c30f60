"""Shared fused convergence runtime — one layer, both engines.

The device-resident ``lax.while_loop`` programs (``core.kcore.fused_convergence``
and its nested-shard_map sibling) were born in the streaming engine (ISSUE 4);
this module lifts their host-side orchestration — staging/padding inputs,
dispatching the right fused program, reconstructing exact per-round
``MessageStats`` arrays from the device stat buffers — into a runtime that
BOTH engines call:

* ``kcore_decompose(..., fused=True)`` / ``kcore_decompose_sharded(...,
  fused=True)`` run the paper's from-scratch decomposition as one jitted
  while_loop (seed = degrees, frontier = everyone);
* ``StreamingKCoreEngine`` (frontier ``fused`` / ``fused_sharded``) runs each
  churn-batch re-convergence the same way (seed = warm-start bound, frontier
  = the batch's touched set).

The contract either way: the returned accounting is bit-equal to what the
host-loop modes would have appended round by round (BZ-verified and
hypothesis-tested), so fusing is purely an execution-placement choice —
never an accounting one.

Every fused run is observable (repro.obs) through always-timed layer
spans: ``fused-converge`` wraps the whole dispatch, with
``device-converge`` (``stage``: host staging and host-to-device copies,
``h2d_bytes`` counted; then ``device-loop``: the compiled while_loop from
its call until ``block_until_ready`` returns) and ``stats-reconstruct``
(the stat-buffer slices and the estimate fetch, and the compiles they
cause) children. ``FusedOutcome.stage_s`` / ``loop_s`` / ``device_s`` /
``reconstruct_s`` are those spans' durations, so benchmark rows get the
breakdown without tracing on; the run's compile count/seconds delta and
its rounds and messages are attached to ``fused-converge``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatch as _dispatch
from repro.core.dispatch import to_device
from repro.core.jit_telemetry import compile_count, compile_seconds
from repro.core.kcore import (
    _fused_sharded_convergence,
    fused_convergence,
    fused_round_stats,
)
from repro.obs import flight, trace


@dataclasses.dataclass
class FusedOutcome:
    """Host-side result of one fused convergence run.

    ``msgs`` / ``changed`` / ``recv`` cover exactly the PRODUCTIVE rounds —
    the arrays a host round loop would have appended — while ``rounds``
    counts every executed superstep including the final unproductive one
    (the host-loop convention).
    """

    est: np.ndarray  # (n,) int32 final estimates (exact cores on convergence)
    rounds: int
    converged: bool
    msgs: np.ndarray  # (k,) int64 messages per productive round
    changed: np.ndarray  # (k,) int64 senders per productive round
    recv: np.ndarray  # (k,) int64 receivers per productive round
    # phase walls, the layer spans' durations (see module docstring):
    device_s: float = 0.0  # staging + the while_loop to device completion
    stage_s: float = 0.0  # ... of which host staging and H2D copies
    loop_s: float = 0.0  # ... of which the compiled while_loop
    reconstruct_s: float = 0.0  # host-side stats/est reconstruction
    compile_delta: int = 0  # fresh XLA compiles this run caused
    compile_s: float = 0.0  # ... and the wall XLA spent on them
    # which superstep implementation ran (repro.core.dispatch): "xla" =
    # generic segment ops, "pallas" = the kernels package. Execution
    # placement only — the accounting above is bit-equal either way.
    dispatch: str = "xla"
    # ids of the (process-local) devices holding shards of the final
    # estimate: one id on a single device, every mesh device when sharded
    devices: tuple = ()


def _shard_devices(x) -> tuple:
    return tuple(sorted({s.device.id for s in x.addressable_shards}))


def _finish(
    span,
    raw,
    dev,
    compiles0,
    csecs0,
    est_of,
    dispatch="xla",
    frontier1=None,
    seed=None,
):
    """Shared tail of both fused paths: reconstruct the per-round stats and
    fetch the estimate inside ``stats-reconstruct``, after the
    ``device-converge`` span ``dev`` has closed."""
    with trace.layer("stats-reconstruct") as rec_span:
        est_j, r, stop, final_act, mb, cb, rb = raw
        # slices of the device buffers by the round count: a count not
        # seen before compiles its slice programs here
        _k, m_r, c_r, r_r, converged = fused_round_stats(r, stop, final_act, mb, cb, rb)
        est = est_of()
        outcome = FusedOutcome(
            est=est,
            rounds=int(r),
            converged=converged,
            msgs=m_r,
            changed=c_r,
            recv=r_r,
            device_s=dev.seconds,
            stage_s=dev.children_s.get("stage", 0.0),
            loop_s=dev.children_s.get("device-loop", 0.0),
            compile_delta=compile_count() - compiles0,
            compile_s=compile_seconds() - csecs0,
            dispatch=dispatch,
            devices=_shard_devices(est_j),
        )
        span.set(
            rounds=outcome.rounds,
            messages=int(outcome.msgs.sum()),
            converged=outcome.converged,
            compile_delta=outcome.compile_delta,
            compile_s=round(outcome.compile_s, 6),
        )
        # flight capture, reconstructed post-hoc from the while_loop stat
        # buffers: exactly the rounds a host loop would have recorded, same
        # accounting arrays. No-op (single attribute read) when disabled.
        rec = flight.recorder()
        if rec.active:
            rec.record_fused_rounds(
                outcome.msgs,
                outcome.changed,
                outcome.recv,
                frontier1=int(frontier1) if frontier1 is not None else (
                    int(outcome.recv[0]) if len(outcome.recv) else 0
                ),
                device_s=outcome.device_s,
                compiles=outcome.compile_delta,
                dispatch=dispatch,
                seed=seed,
                final=est,
            )
    outcome.reconstruct_s = rec_span.seconds
    return outcome


def _device_loop(program, *args, **static):
    """Run a compiled fused program to device completion inside a
    ``device-loop`` layer span."""
    with trace.layer("device-loop"):
        out = program(*args, **static)
        # block INSIDE the span: the async dispatch returns immediately,
        # and without the sync the device wall would be misattributed to
        # whichever np.asarray happens to touch a result first
        return jax.block_until_ready(out)


def fused_converge_dense(
    seed, active, src, dst, arc_mask, deg, *, n, n_iters, max_rounds, dispatch=None, ell=None, frontier1=None
):
    """Single-device fused convergence over (padded) arc arrays.

    ``src``/``dst``/``arc_mask`` may be numpy or already-device arrays; the
    streaming engine passes its pow2 high-water padded CSR slots, the static
    engine the plain sorted-COO arrays (every arc live).

    ``dispatch`` picks the superstep implementation inside the while_loop
    (``repro.core.dispatch``): None/"auto" consults the platform layer
    (``REPRO_PALLAS``), "pallas"/"xla" force it. With the Pallas plan the
    per-round reductions run through the kernels package — and through the
    ``kcore_hindex`` ELL kernel when the caller passes the static
    degree-bucketed ``ell`` layout (from-scratch decompositions only; the
    streaming engine's masked slot arrays stay on the segment-sum route).
    Accounting is bit-equal across every dispatch choice.
    """
    compiles0, csecs0 = compile_count(), compile_seconds()
    plan = _dispatch.resolve_plan(dispatch)
    # flight bookkeeping resolved up front, BEFORE device work: the
    # accounting round-1 frontier (callers override when their while_loop
    # activation differs from the accounting convention) and a host copy
    # of the seed for the aggregate drop histogram. Zero work when the
    # recorder is disabled.
    rec = flight.recorder()
    seed_np = None
    if rec.active:
        if frontier1 is None:
            frontier1 = int(np.asarray(active).sum())
        seed_np = np.asarray(seed, np.int64).copy()
    with trace.layer("fused-converge", n=n, max_rounds=max_rounds, dispatch=plan.kind) as span:
        with trace.layer("device-converge") as dev:
            if plan.kind == "pallas":
                # the graph operands are staged in their own ``stage`` span
                prog = _dispatch.fused_convergence_program(
                    n,
                    n_iters,
                    max_rounds,
                    plan,
                    np.asarray(src, np.int32),
                    np.asarray(dst, np.int32),
                    ell=ell,
                )
                with trace.layer("stage", h2d_bytes=0) as st:
                    args = (
                        to_device(st, seed, jnp.int32),
                        to_device(st, arc_mask),
                        to_device(st, active),
                        to_device(st, deg, jnp.int32),
                    )
                out = _device_loop(prog, *args)
            else:
                with trace.layer("stage", h2d_bytes=0) as st:
                    args = (
                        to_device(st, seed, jnp.int32),
                        to_device(st, src, jnp.int32),
                        to_device(st, dst, jnp.int32),
                        to_device(st, arc_mask),
                        to_device(st, active),
                        to_device(st, deg, jnp.int32),
                    )
                out = _device_loop(fused_convergence, *args, n=n, n_iters=n_iters, max_rounds=max_rounds)
        return _finish(
            span,
            out,
            dev,
            compiles0,
            csecs0,
            lambda: np.asarray(out[0], np.int32),
            dispatch=plan.kind,
            frontier1=frontier1,
            seed=seed_np,
        )


def fused_converge_sharded(seed, active, sg, mesh, axis_names, *, n, n_iters, max_rounds, frontier1=None):
    """Fused convergence with the masked shard_map superstep nested inside.

    ``sg`` is a ``repro.graph.partition.ShardedGraph`` (from ``shard_graph``
    for the static engine, ``shard_arc_arrays`` over live CSR slots for the
    streaming engine); ``seed``/``active`` are plain (n,) host vectors and
    are padded/reshaped to the shard layout here.

    The mesh may span PROCESSES (``compat.init_multiprocess`` +
    ``compat.global_mesh``): every rank calls this with the same graph and
    the same host vectors (SPMD — the graph is cheap to hold per host, the
    device arrays are what's sharded), inputs are staged as global arrays
    through ``compat.stage_to_mesh``, and the sharded estimate output comes
    back through ``compat.fetch_replicated``. The stat buffers are
    replicated outputs, so their host reads stay process-local. Accounting
    is bit-equal to every single-process mode either way (asserted rank-side
    in tests/test_multihost.py).
    """
    from jax.sharding import PartitionSpec as P

    from repro.distribution import compat

    compiles0, csecs0 = compile_count(), compile_seconds()
    rec = flight.recorder()
    seed_np = None
    if rec.active:
        if frontier1 is None:
            frontier1 = int(np.asarray(active).sum())
        seed_np = np.asarray(seed, np.int64).copy()
    multiproc = compat.is_multiprocess_mesh(mesh)
    axes = tuple(axis_names)

    def stage(st, a):
        if multiproc:
            a = np.asarray(a)
            st.add("h2d_bytes", a.nbytes)
            return compat.stage_to_mesh(a, mesh, P(axes))
        return to_device(st, a)

    with trace.layer("fused-converge", n=n, max_rounds=max_rounds,
                     mesh_devices=sg.n_shards, multiprocess=multiproc) as span:
        prog = _fused_sharded_convergence(
            mesh, axes, sg.verts_per_shard, n_iters, max_rounds
        )
        n_dev, V = sg.n_shards, sg.verts_per_shard
        with trace.layer("device-converge") as dev:
            with trace.layer("stage", h2d_bytes=0) as st:
                est_p = np.zeros(sg.n_pad, np.int32)
                est_p[:n] = seed
                act_p = np.zeros(sg.n_pad, bool)
                act_p[:n] = active
                args = (
                    stage(st, est_p.reshape(n_dev, V)),
                    stage(st, sg.src),
                    stage(st, sg.dst),
                    stage(st, sg.arc_mask),
                    stage(st, sg.deg),
                    stage(st, act_p.reshape(n_dev, V)),
                )
            out = _device_loop(prog, *args)
        return _finish(
            span,
            out,
            dev,
            compiles0,
            csecs0,
            lambda: compat.fetch_replicated(out[0], mesh)
            .reshape(-1)[:n].astype(np.int32),
            frontier1=frontier1,
            seed=seed_np,
        )
