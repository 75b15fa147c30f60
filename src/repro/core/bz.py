"""Batagelj–Zaversnik sequential k-core decomposition — the paper's baseline.

O(n + m) bucket-sort peeling, exactly as reviewed in the paper's §I: the
sequential algorithm the distributed one is compared against, and our oracle
for every correctness test. No JAX. The peeling loop runs over Python lists
and an int32 memoryview of the arcs: numpy scalar indexing inside the loop
costs ~4x more, which at 10^8 arcs is minutes.
"""

from __future__ import annotations

import numpy as np

from repro.graph.structs import Graph


def bz_core_numbers(g: Graph) -> np.ndarray:
    """Exact core numbers via BZ bucket peeling."""
    n = g.n
    if n == 0:
        return np.zeros(0, np.int32)
    deg0 = g.deg.astype(np.int64)
    md = int(deg0.max())

    # bucket sort vertices by degree (stable: ties in vertex order)
    bin_start = np.zeros(md + 2, np.int64)
    np.cumsum(np.bincount(deg0, minlength=md + 1), out=bin_start[1:])
    vert_np = np.argsort(deg0, kind="stable")
    pos_np = np.empty(n, np.int64)
    pos_np[vert_np] = np.arange(n)
    deg = deg0.tolist()
    vert = vert_np.tolist()              # vertices sorted by current degree
    pos = pos_np.tolist()                # position of vertex in vert[]
    bin_ptr = bin_start[:-1].tolist()    # start index of each degree bucket
    offsets = g.offsets.tolist()
    dst = memoryview(np.ascontiguousarray(g.dst, np.int32)).cast("B").cast("i")

    # once v is reached its degree is final: only neighbors of strictly
    # larger current degree are decremented, so deg ends as the core numbers
    for i in range(n):
        v = vert[i]
        dv = deg[v]
        for u in dst[offsets[v]:offsets[v + 1]]:
            du = deg[u]
            if du > dv:
                pu = pos[u]
                pw = bin_ptr[du]
                if pu != pw:             # swap u to the front of its bucket
                    w = vert[pw]
                    pos[u], pos[w] = pw, pu
                    vert[pu], vert[pw] = w, u
                bin_ptr[du] = pw + 1
                deg[u] = du - 1
    return np.asarray(deg, np.int32)


def max_core(g: Graph) -> int:
    c = bz_core_numbers(g)
    return int(c.max()) if len(c) else 0
