"""Faults planted under the timed path, to show that ``correct`` catches them.

Each entry is a context manager that patches the program for the length
of one run; the benchmark's own runs never use them. ``round_cap`` is the
control (the configuration states no precision, so the control breaks the
guarantee it does state, exact core numbers): a fixed budget of supersteps
that stops short of the fixpoint, the shortcut a later change might be
tempted by. The others are the faults a one-chip cell can have: a step
that returns its state unchanged, half of the batch left out, and an
answer altered where it is produced.

Used by ``bench/control.py`` on the chip and by the tests on the CPU.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from unittest import mock

import numpy as np


@contextlib.contextmanager
def round_cap(decompose_rounds: int, update_rounds: int = 2):
    """Decompositions stop after ``decompose_rounds`` supersteps, churn
    batches after ``update_rounds``."""
    import repro.streaming as streaming
    from repro.core import KCoreConfig, kcore_decompose
    from repro.launch import kcore_run

    def decompose(args, g):
        res = kcore_decompose(g, KCoreConfig(max_rounds=decompose_rounds), fused=args.fused)
        return res, 0.0

    capped = functools.partial(streaming.StreamingConfig, max_rounds=update_rounds)
    with mock.patch.object(kcore_run, "decompose", decompose), \
            mock.patch.object(streaming, "StreamingConfig", capped):
        yield


@contextlib.contextmanager
def unchanged_state():
    """A decomposition returns its seed (the degrees); a churn batch is
    patched into the graph but the cores stay as they were."""
    from repro.launch import kcore_run
    from repro.streaming.engine import StreamingKCoreEngine

    orig_d, orig_b = kcore_run.decompose, StreamingKCoreEngine.apply_batch

    def decompose(args, g):
        res, wall = orig_d(args, g)
        return dataclasses.replace(res, core=np.asarray(g.deg, np.int32).copy()), wall

    def apply_batch(self, batch):
        old = self.core
        res = orig_b(self, batch)
        self.core = old
        return dataclasses.replace(res, core=old)

    with mock.patch.object(kcore_run, "decompose", decompose), \
            mock.patch.object(StreamingKCoreEngine, "apply_batch", apply_batch):
        yield


@contextlib.contextmanager
def half_batch():
    """A decomposition sees only the first half of the graph's edges; a
    churn batch applies only the first half of its inserts and deletes."""
    from repro.graph.structs import Graph
    from repro.launch import kcore_run
    from repro.streaming import EdgeBatch
    from repro.streaming.server import KCoreServer

    orig_d, orig_u = kcore_run.decompose, KCoreServer.update

    def decompose(args, g):
        half = g.src < g.dst
        edges = np.stack([g.src[half], g.dst[half]], axis=1)
        return orig_d(args, Graph.from_edges(edges[: edges.shape[0] // 2], n=g.n))

    def update(self, batch):
        kept = EdgeBatch.make(insert=batch.insert[: batch.insert.shape[0] // 2],
                              delete=batch.delete[: batch.delete.shape[0] // 2])
        return orig_u(self, kept)

    with mock.patch.object(kcore_run, "decompose", decompose), \
            mock.patch.object(KCoreServer, "update", update):
        yield


@contextlib.contextmanager
def altered_answer():
    """One vertex's core number is off by one where the engine produces it."""
    from repro.launch import kcore_run
    from repro.streaming.engine import StreamingKCoreEngine

    orig_d, orig_b = kcore_run.decompose, StreamingKCoreEngine.apply_batch

    def decompose(args, g):
        res, wall = orig_d(args, g)
        core = np.array(res.core, np.int32)
        core[0] += 1
        return dataclasses.replace(res, core=core), wall

    def apply_batch(self, batch):
        res = orig_b(self, batch)
        core = np.array(res.core, np.int32)
        core[0] += 1
        self.core = core
        return dataclasses.replace(res, core=core)

    with mock.patch.object(kcore_run, "decompose", decompose), \
            mock.patch.object(StreamingKCoreEngine, "apply_batch", apply_batch):
        yield


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "altered_answer": altered_answer}
