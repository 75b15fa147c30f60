"""Jit'd wrapper for the kcore_hindex Pallas kernel.

Handles row padding to the tile multiple, 2-D reshape of the estimate
column, VMEM-aware row-tile selection, and interpret mode on the CPU backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import platform as _platform
from repro.kernels.kcore_hindex.kernel import hindex_rows_pallas

_VMEM_BUDGET_BYTES = 4 * 1024 * 1024  # per-block neighbor tile budget


def _pick_row_tile(width: int) -> int:
    rows = _VMEM_BUDGET_BYTES // max(width * 4, 1)
    rows = max(8, min(256, rows))
    return 1 << (rows.bit_length() - 1)  # round down to power of two


@functools.partial(jax.jit, static_argnames=("n_iters",))
def hindex_rows(nbr_est, est_u, n_iters: int):
    """Rowwise clipped h-index. nbr_est (R, W) int32, est_u (R,) int32 → (R,).

    Checked against the sort-based oracle ``ref.hindex_rows_ref``.
    """
    rows, width = nbr_est.shape
    tile = _pick_row_tile(width)
    pad = (-rows) % tile
    if pad:
        nbr_est = jnp.pad(nbr_est, ((0, pad), (0, 0)))
        est_u = jnp.pad(est_u, (0, pad))
    out = hindex_rows_pallas(
        nbr_est,
        est_u[:, None],
        n_iters=n_iters,
        row_tile=tile,
        interpret=_platform.interpret_kernels(),
    )
    return out[:rows, 0]
