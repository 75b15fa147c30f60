"""Pallas TPU kernel: rowwise clipped h-index — the k-core round hot spot.

Input is the degree-bucketed ELL tile (rows × width neighbor-estimate
window, already gathered; sentinel slots hold 0) plus each row's current
estimate. Output is the new estimate

    h(u) = max k in [0, est_u] s.t. |{j : min(vals[u,j], est_u) >= k}| >= k.

TPU mapping: the whole (TR, W) tile lives in VMEM; the h-index is computed by
a branch-free vectorized binary search — each probe is one VPU compare +
row-reduction, ``n_iters = ceil(log2(maxdeg+1))+1`` probes. No sort, no
scatter, no data-dependent control flow: this is the paper's per-vertex
``updateCore`` procedure reshaped into rectangular SIMD work.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _hindex_kernel(nbr_ref, estu_ref, out_ref, *, n_iters: int):
    vals = nbr_ref[...]  # (TR, W) int32
    est_u = estu_ref[...]  # (TR, 1) int32
    vals = jnp.minimum(vals, est_u)  # clip at own estimate

    lo = jnp.zeros_like(est_u)
    hi = est_u

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi + 1) // 2  # probe k (>= 1 when hi > lo)
        k = jnp.maximum(mid, 1)
        cnt = jnp.sum((vals >= k).astype(jnp.int32), axis=1, keepdims=True)
        ok = cnt >= mid
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    lo, _ = lax.fori_loop(0, n_iters, body, (lo, hi))
    out_ref[...] = lo


def hindex_rows_pallas(nbr_est, est_u2d, *, n_iters: int, row_tile: int, interpret: bool):
    """nbr_est: (R, W) int32 (R % row_tile == 0), est_u2d: (R, 1) int32."""
    rows, width = nbr_est.shape
    grid = (rows // row_tile,)
    return pl.pallas_call(
        functools.partial(_hindex_kernel, n_iters=n_iters),
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_tile, width), lambda i: (i, 0)),
            pl.BlockSpec((row_tile, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 1), jnp.int32),
        interpret=interpret,
        name="kcore_hindex",
    )(nbr_est, est_u2d)
