"""The benchmark's traffic: churn batches.

Everything here is drawn from the mix file's parameters; none of it
imports the program. The churn stream keeps its own mirror of the edge set
(sorted canonical keys), so each batch is drawn from the graph as it
stands and the reference can rebuild every fixpoint later.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.graphs import CSR, keys_of


def stream_rng(seed: int, stream: str, index: int = 0) -> np.random.Generator:
    """An independent generator for one named stream of one run."""
    tag = int.from_bytes(stream.encode(), "little") % (1 << 63)
    return np.random.default_rng([int(seed), tag, int(index)])


@dataclasses.dataclass
class Batch:
    insert: np.ndarray  # (bi, 2) int64 edges absent before the batch
    delete: np.ndarray  # (bd, 2) int64 existing edges
    keys_after: np.ndarray  # sorted canonical keys of the graph after it


class ChurnStream:
    """Balanced churn by the protocol of the core-maintenance literature
    (Sariyuce et al., PVLDB 6(6), 2013: uniformly chosen existing edges
    removed and inserted back): each batch deletes ``frac/2`` of the
    configuration's edge count, uniformly over the current edges, and
    inserts back the edges the batch before it deleted. The graph never drifts from the configuration's by more
    than one batch's deletions, so its degree law stays the source's.
    Same semantics as the server: deletes first, then inserts."""

    def __init__(self, g: CSR, frac: float, seed: int):
        self.n = g.n
        self.frac = float(frac)
        self.seed = int(seed)
        self.keys = keys_of(g)
        self.pending = np.zeros(0, np.int64)  # keys deleted by the last batch
        self.batches: list[Batch] = []

    def next_batch(self) -> Batch:
        i = len(self.batches)
        rng = stream_rng(self.seed, "churn", i)
        m = self.keys.size + self.pending.size  # the configuration's count
        b = max(2, int(self.frac * m))
        gone = rng.choice(self.keys.size, size=min(b - b // 2, self.keys.size), replace=False)
        keep = np.ones(self.keys.size, bool)
        keep[gone] = False
        back = self.pending
        self.pending = self.keys[gone]
        keys = np.union1d(self.keys[keep], back)
        batch = Batch(insert=np.stack(np.divmod(back, self.n), axis=1),
                      delete=np.stack(np.divmod(self.pending, self.n), axis=1),
                      keys_after=keys)
        self.keys = keys
        self.batches.append(batch)
        return batch
