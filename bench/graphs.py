"""The benchmark's own graph generation: Graph 500 Kronecker graphs,
relabelling, cache.

The Graph 500 specification's generator: ``edgefactor * 2**scale`` edges,
each placed by ``scale`` recursive choices of a quadrant of the adjacency
matrix with probabilities A, B, C and D = 1 - A - B - C, after which the
vertex labels are randomly permuted. The quadrant sampling is copied from
the program's R-MAT generator, so that no later change to the program can
move the yardstick. A configuration names these parameters and a fixed
graph seed: the graph is the deployment's data and is the same for every
run, while ``--seed`` relabels it and drives the traffic.

A graph is held here in canonical CSR form, with no reference to the
program: ``n``, ``offsets`` (n+1, int64) and ``dst`` (2m, int32); a
generated graph lists each vertex's neighbours in ascending order, a
relabelled one in the order of the rows it came from.
"""

from __future__ import annotations

import dataclasses
import pathlib

import numpy as np

RELABEL_GROUP = 128


@dataclasses.dataclass(frozen=True)
class CSR:
    n: int
    offsets: np.ndarray  # (n+1,) int64
    dst: np.ndarray  # (2m,) int32, row by row

    @property
    def m(self) -> int:
        return int(self.dst.shape[0]) // 2

    @property
    def deg(self) -> np.ndarray:
        return np.diff(self.offsets).astype(np.int32)

    @property
    def src(self) -> np.ndarray:
        return np.repeat(np.arange(self.n, dtype=np.int32), self.deg)


def rmat_edges(bits: int, edge_factor: int, seed: int, a: float, b: float, c: float) -> np.ndarray:
    """Raw quadrant draws: (n * edge_factor, 2) int64 with loops and repeats."""
    n = 1 << bits
    m = n * edge_factor
    rng = np.random.default_rng(seed)
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(bits):
        r = rng.random(m)
        go_right = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        go_down = r >= a + b
        src |= go_down.astype(np.int64) << bit
        dst |= go_right.astype(np.int64) << bit
    return np.stack([src, dst], axis=1)


def csr_from_keys(n: int, keys: np.ndarray) -> CSR:
    """CSR of the undirected simple graph whose canonical edges (lo < hi)
    are ``keys = lo * n + hi`` (sorted, unique)."""
    lo, hi = np.divmod(np.asarray(keys, np.int64), n)
    arcs = np.concatenate([lo * n + hi, hi * n + lo])
    arcs.sort()
    src, dst = np.divmod(arcs, n)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=offsets[1:])
    return CSR(n=n, offsets=offsets, dst=dst.astype(np.int32))


def edge_keys(n: int, pairs: np.ndarray) -> np.ndarray:
    """Sorted unique canonical keys of ``pairs``: no loops, no repeats,
    direction dropped (the paper's dataCleanse rules)."""
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    pairs = pairs[pairs[:, 0] != pairs[:, 1]]
    lo = np.minimum(pairs[:, 0], pairs[:, 1])
    hi = np.maximum(pairs[:, 0], pairs[:, 1])
    return np.unique(lo * n + hi)


def keys_of(g: CSR) -> np.ndarray:
    """Sorted canonical edge keys of ``g``."""
    src = g.src.astype(np.int64)
    half = src < g.dst
    return np.sort(src[half] * g.n + g.dst[half])


def kronecker(scale: int, edgefactor: int, seed: int, a: float, b: float, c: float) -> CSR:
    """The simple undirected graph of the raw draws, in generated labels."""
    n = 1 << scale
    return csr_from_keys(n, edge_keys(n, rmat_edges(scale, edgefactor, seed, a, b, c)))


def generate(config: dict) -> CSR:
    """The configuration's graph: its Kronecker draws (``scale``,
    ``edgefactor``, ``A``, ``B``, ``C``, ``graph_seed``), labels permuted
    by a permutation drawn from the same seed, as the specification asks."""
    if config["generator"] != "graph500":
        raise ValueError(f"unknown generator {config['generator']!r}")
    scale, seed = int(config["scale"]), int(config["graph_seed"])
    g = kronecker(scale, int(config["edgefactor"]), seed,
                  float(config["A"]), float(config["B"]), float(config["C"]))
    perm = np.random.default_rng([seed, 1]).permutation(g.n)
    return csr_from_keys(g.n, edge_keys(g.n, np.stack([perm[g.src], perm[g.dst]], axis=1)))


def load_or_generate(config: dict, cache: pathlib.Path) -> CSR:
    """The configuration's graph, generated once per checkout and then
    read back from ``cache`` (an .npz file)."""
    if cache.exists():
        with np.load(cache) as z:
            return CSR(n=int(z["n"]), offsets=z["offsets"], dst=z["dst"])
    g = generate(config)
    cache.parent.mkdir(parents=True, exist_ok=True)
    tmp = cache.with_suffix(".tmp.npz")
    np.savez(tmp, n=g.n, offsets=g.offsets, dst=g.dst)
    tmp.replace(cache)
    return g


def group_permutation(n: int, rng: np.random.Generator) -> np.ndarray:
    """A permutation ``p`` of range(n) that maps every aligned group of
    ``RELABEL_GROUP`` ids onto itself (new id of vertex u is p[u]).

    Degrees per group stay where they were, so any layout that buckets
    vertices by degree or counts arcs per row block of a multiple of 128
    ids sees the same shapes, while the arcs themselves differ."""
    if n % RELABEL_GROUP:
        raise ValueError(f"n={n} is not a multiple of {RELABEL_GROUP}")
    base = np.arange(n, dtype=np.int64).reshape(-1, RELABEL_GROUP)
    return rng.permuted(base, axis=1).reshape(-1)


def relabel(g: CSR, p: np.ndarray) -> CSR:
    """``g`` with vertex u renamed p[u]; rows in new-id order, neighbour
    lists in the order of the old rows (not re-sorted)."""
    n = g.n
    q = np.empty(n, np.int64)
    q[p] = np.arange(n)
    deg = np.diff(g.offsets)[q]
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=offsets[1:])
    idx = np.repeat(g.offsets[q] - offsets[:-1], deg) + np.arange(offsets[-1])
    dst = p[g.dst[idx]].astype(np.int32)
    return CSR(n=n, offsets=offsets, dst=dst)
