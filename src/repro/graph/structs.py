"""Core graph data structures.

The paper's Go implementation stores, per vertex-goroutine, a neighbor channel
list. On TPU we replace pointer-chasing with two dense layouts:

  * COO/CSR ("segment") layout — arcs (both directions of every undirected
    edge) sorted by source, with CSR offsets. All vertex-centric updates are
    `jax.ops.segment_sum` over the arc array.
  * Degree-bucketed ELL layout — vertices bucketed by degree, neighbor lists
    padded to the bucket width, producing rectangular (rows × width) tiles
    that map onto VMEM/VPU. This feeds the Pallas `kcore_hindex` kernel.

Construction follows the paper's dataCleanse rules (§III.A / §IV.B):
no self-loops, no multi-edges, directed input symmetrized to undirected.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from repro.graph.padding import round_up as _round_up  # shared padding policy


@dataclasses.dataclass(frozen=True)
class Graph:
    """Undirected graph in sorted-COO + CSR form (numpy, host-side)."""

    n: int                 # number of vertices
    m: int                 # number of undirected edges
    src: np.ndarray        # (2m,) int32 — arc sources, sorted ascending
    dst: np.ndarray        # (2m,) int32 — arc destinations
    offsets: np.ndarray    # (n+1,) int64 — CSR row offsets into src/dst
    deg: np.ndarray        # (n,) int32  — vertex degrees

    # ------------------------------------------------------------------ #
    @classmethod
    def from_edges(cls, edges: np.ndarray | Sequence[tuple[int, int]],
                   n: int | None = None) -> "Graph":
        """Build from an (E, 2) array of (possibly directed / duplicated)
        edges, applying the paper's dataCleanse rules."""
        edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
        if edges.size == 0:
            nn = int(n or 0)
            return cls(
                n=nn, m=0,
                src=np.zeros(0, np.int32), dst=np.zeros(0, np.int32),
                offsets=np.zeros(nn + 1, np.int64), deg=np.zeros(nn, np.int32),
            )
        # Rule 1: a vertex cannot connect to itself.
        edges = edges[edges[:, 0] != edges[:, 1]]
        # Rule 3 (symmetrize): undirected — keep canonical (min, max) ...
        lo = np.minimum(edges[:, 0], edges[:, 1])
        hi = np.maximum(edges[:, 0], edges[:, 1])
        top = int(hi.max()) + 1 if hi.size else 0
        nn = int(n if n is not None else top)
        # ... encoded as one int64 key lo * base + hi, so that dedupe and the
        # arc sort below are 1-D sorts (a row-wise unique over (E, 2) costs
        # ~15x more at 10^8 arcs)
        base = max(nn, top, 1)
        # Rule 2: each pair connects with at most one edge.
        key = np.unique(lo * base + hi)
        m = int(key.size)
        lo, hi = np.divmod(key, base)
        # Both arc directions, sorted by src (ties by dst for determinism).
        arcs = np.concatenate([key, hi * base + lo])
        arcs.sort()
        src, dst = np.divmod(arcs, base)
        src, dst = src.astype(np.int32), dst.astype(np.int32)
        deg = np.bincount(src, minlength=nn).astype(np.int32)
        offsets = np.zeros(nn + 1, np.int64)
        np.cumsum(deg, out=offsets[1:])
        return cls(n=nn, m=m, src=src, dst=dst, offsets=offsets, deg=deg)

    # ------------------------------------------------------------------ #
    @property
    def num_arcs(self) -> int:
        return int(self.src.shape[0])

    @property
    def max_deg(self) -> int:
        return int(self.deg.max()) if self.n else 0

    @property
    def avg_deg(self) -> float:
        return float(self.deg.mean()) if self.n else 0.0

    def neighbors(self, u: int) -> np.ndarray:
        return self.dst[self.offsets[u]:self.offsets[u + 1]]

    def validate(self) -> None:
        assert self.src.shape == self.dst.shape
        assert self.num_arcs == 2 * self.m
        assert (self.src[:-1] <= self.src[1:]).all(), "arcs must be sorted by src"
        assert int(self.deg.sum()) == self.num_arcs
        assert self.offsets[-1] == self.num_arcs


# ---------------------------------------------------------------------- #
# Shard padding
# ---------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class PaddedGraph:
    """Graph padded so vertex count and arc count divide a shard count.

    Padding arcs use src = dst = n_pad - 1 only if a padding vertex exists;
    they always point at the *sentinel* vertex (index ``n_real``.. are
    padding, degree 0, estimate 0) so they never change a real count:
    a padding arc contributes to the segment of a padding vertex only.
    """

    n_real: int
    n_pad: int            # padded vertex count (multiple of shards)
    num_arcs_real: int
    num_arcs_pad: int     # padded arc count (multiple of shards)
    src: np.ndarray       # (num_arcs_pad,) int32
    dst: np.ndarray       # (num_arcs_pad,) int32
    deg: np.ndarray       # (n_pad,) int32, zeros in padding
    arc_mask: np.ndarray  # (num_arcs_pad,) bool — True for real arcs


def pad_graph_for_shards(g: Graph, n_shards: int) -> PaddedGraph:
    """Pad vertices and arcs to multiples of ``n_shards``.

    Arc padding is appended at the end with src pointing into the padding
    vertex range, keeping the src-sorted property (padding vertices have the
    largest indices).
    """
    n_pad = max(_round_up(g.n, n_shards), n_shards)
    arcs_pad = max(_round_up(g.num_arcs, n_shards), n_shards)
    extra = arcs_pad - g.num_arcs
    sentinel = n_pad - 1  # a padding vertex (deg 0) unless n_pad == n; then
    # fall back to a self-arc on the last vertex which is masked & points to
    # a zero-degree contribution via arc_mask handling in the engine.
    src = np.concatenate([g.src, np.full(extra, sentinel, np.int32)])
    dst = np.concatenate([g.dst, np.full(extra, sentinel, np.int32)])
    deg = np.concatenate([g.deg, np.zeros(n_pad - g.n, np.int32)])
    mask = np.concatenate([np.ones(g.num_arcs, bool), np.zeros(extra, bool)])
    return PaddedGraph(
        n_real=g.n, n_pad=n_pad,
        num_arcs_real=g.num_arcs, num_arcs_pad=arcs_pad,
        src=src, dst=dst, deg=deg, arc_mask=mask,
    )


# ---------------------------------------------------------------------- #
# Degree-bucketed ELL layout (Pallas hot path)
# ---------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class EllBucket:
    width: int            # padded neighbor-list width (power of two-ish)
    ids: np.ndarray       # (rows,) int32 vertex ids (padded rows use n — the
                          # sentinel row; their results are discarded)
    nbrs: np.ndarray      # (rows, width) int32 neighbor ids, padding = n
    rows_real: int


@dataclasses.dataclass(frozen=True)
class EllGraph:
    """Degree-bucketed ELL: per bucket a dense (rows, width) neighbor table.

    Estimate lookups use an extended estimate vector ``est_ext`` of length
    n + 1 whose last entry is 0 (the sentinel), so padded neighbor slots never
    satisfy ``est >= k`` for k >= 1.
    """

    n: int
    buckets: tuple[EllBucket, ...]

    @property
    def padded_slots(self) -> int:
        return sum(b.nbrs.size for b in self.buckets)

    @property
    def fill_ratio(self) -> float:
        real = sum(int((b.nbrs != self.n).sum()) for b in self.buckets)
        return real / max(self.padded_slots, 1)


def build_ell(g: Graph, widths: Sequence[int] = (8, 32, 128, 512, 2048),
              row_multiple: int = 8) -> EllGraph:
    """Bucket vertices by degree; pad neighbor lists to the bucket width.

    Vertices with degree above the largest width land in further buckets
    4x wider each, the last sized to the max degree rounded up to 128.
    One bucket at the max degree would pad every hub to the largest hub:
    on the soc-LiveJournal1 analogue (max degree 153,469) that is 1.9G
    slots instead of 0.3G. Degree-0 vertices are skipped — their core
    number is 0 and the engine fixes them up directly.
    """
    widths = sorted(set(int(w) for w in widths))
    if g.n == 0:
        return EllGraph(n=0, buckets=())
    maxd = g.max_deg
    while maxd > 4 * widths[-1]:
        widths.append(4 * widths[-1])
    if maxd > widths[-1]:
        widths.append(_round_up(maxd, 128))
    buckets: list[EllBucket] = []
    degs = g.deg
    # Per-arc column index = position of the arc within its source's CSR row.
    arc_col = np.arange(g.num_arcs, dtype=np.int64) - g.offsets[g.src]
    lo = 1
    for w in widths:
        sel = np.where((degs >= lo) & (degs <= w))[0]
        lo = w + 1
        if sel.size == 0:
            continue
        rows = max(_round_up(sel.size, row_multiple), row_multiple)
        ids = np.full(rows, g.n, np.int32)
        ids[: sel.size] = sel.astype(np.int32)
        # Vectorized fill: row index of each selected vertex, gathered per arc.
        row_of = np.full(g.n, -1, np.int64)
        row_of[sel] = np.arange(sel.size)
        arc_sel = row_of[g.src] >= 0
        nbrs = np.full((rows, w), g.n, np.int32)
        nbrs[row_of[g.src[arc_sel]], arc_col[arc_sel]] = g.dst[arc_sel]
        buckets.append(EllBucket(width=w, ids=ids, nbrs=nbrs,
                                 rows_real=int(sel.size)))
    return EllGraph(n=g.n, buckets=tuple(buckets))
