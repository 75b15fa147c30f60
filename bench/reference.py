"""Plain references for the k-core cells, independent of the program.

* ``bz_cores`` — Batagelj–Zaversnik bucket peeling, the exact core numbers.
* ``jacobi_bills`` — the paper's synchronous locality iteration (every
  vertex replaces its estimate by the h-index of its neighbours' estimates
  clipped at its own, starting from the degrees) with the paper's message
  and active-node accounting: round 0 broadcasts every degree (2m messages,
  all n vertices active); in round r >= 1 every vertex whose estimate fell
  sends deg(u) messages, and a vertex is active in round r + 1 iff it
  received a message in round r. Only active vertices are recomputed: a
  vertex none of whose inputs changed keeps its h-index (the operator is
  monotone and its own decrease is already the h-index of its inputs), so
  this equals recomputing everyone, at a fraction of the cost.

Both work on ``bench.graphs.CSR`` and numpy alone.
"""

from __future__ import annotations

import numpy as np

from bench.graphs import CSR


def bz_cores(g: CSR) -> np.ndarray:
    """Exact core numbers by bucket peeling (O(m))."""
    n = g.n
    if n == 0:
        return np.zeros(0, np.int32)
    deg0 = np.diff(g.offsets)
    md = int(deg0.max())
    bin_start = np.zeros(md + 2, np.int64)
    np.cumsum(np.bincount(deg0, minlength=md + 1), out=bin_start[1:])
    vert_np = np.argsort(deg0, kind="stable")
    pos_np = np.empty(n, np.int64)
    pos_np[vert_np] = np.arange(n)
    deg = deg0.tolist()
    vert = vert_np.tolist()
    pos = pos_np.tolist()
    bin_ptr = bin_start[:-1].tolist()
    offsets = g.offsets.tolist()
    dst = g.dst.tolist()
    for i in range(n):
        v = vert[i]
        dv = deg[v]
        for u in dst[offsets[v]:offsets[v + 1]]:
            du = deg[u]
            if du > dv:
                pu = pos[u]
                pw = bin_ptr[du]
                if pu != pw:
                    w = vert[pw]
                    pos[u], pos[w] = pw, pu
                    vert[pu], vert[pw] = w, u
                bin_ptr[du] = pw + 1
                deg[u] = du - 1
    return np.asarray(deg, np.int32)


def _rows(g: CSR, rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(row index per arc, arc index) of the arcs of ``rows``, row-major."""
    deg = np.diff(g.offsets)[rows]
    starts = np.zeros(rows.size + 1, np.int64)
    np.cumsum(deg, out=starts[1:])
    arc = np.repeat(g.offsets[rows] - starts[:-1], deg) + np.arange(starts[-1])
    return np.repeat(np.arange(rows.size), deg), arc


def _hindex(g: CSR, est: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """h-index of {min(est[v], est[u]) : v ~ u} for each u in ``rows``."""
    row_of, arc = _rows(g, rows)
    own = est[rows]
    vals = np.minimum(est[g.dst[arc]], own[row_of]).astype(np.int64)
    top = int(vals.max()) + 1 if vals.size else 1
    order = np.argsort(row_of * top + (top - 1 - vals), kind="stable")
    vals = vals[order]
    first = np.zeros(rows.size + 1, np.int64)
    np.cumsum(np.bincount(row_of, minlength=rows.size), out=first[1:])
    rank = np.arange(vals.size) - np.repeat(first[:-1], np.diff(first)) + 1
    return np.bincount(row_of, weights=vals >= rank, minlength=rows.size).astype(np.int32)


def jacobi_bills(g: CSR) -> dict:
    """Cores, supersteps and per-round bills of the synchronous iteration.

    ``rounds`` counts every superstep run, the last (unproductive) one
    included; ``messages`` and ``active`` hold round 0 and one entry per
    productive round."""
    deg = np.diff(g.offsets)
    est = deg.astype(np.int32)
    messages = [int(deg.sum())]
    active = [g.n, int((deg > 0).sum())]
    frontier = np.arange(g.n)
    rounds = 0
    while frontier.size:
        new = _hindex(g, est, frontier)
        rounds += 1
        fell = new < est[frontier]
        if not fell.any():
            break
        changed = frontier[fell]
        est[changed] = new[fell]
        messages.append(int(deg[changed].sum()))
        hit = np.zeros(g.n, bool)
        row_of, arc = _rows(g, changed)
        hit[g.dst[arc]] = True
        frontier = np.flatnonzero(hit)
        active.append(int(frontier.size))
    return {
        "core": est,
        "rounds": rounds,
        "messages": np.asarray(messages, np.int64),
        "active": np.asarray(active[: len(messages)], np.int64),
    }
