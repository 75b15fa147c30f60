"""Layout builder + wrappers for the blocked segment-sum kernel.

Two kinds of values go through the kernel:

* floating ``vals`` — summed in f32 (the one-hot matmul at HIGHEST
  precision);
* bool ``vals`` — 0/1 indicators, counted exactly: they enter the MXU as
  bf16 0/1, accumulate in f32 and come back as int32. An f32 sum of ones is
  exact below 2^24, so ``blocked_layout`` refuses any layout whose largest
  segment could reach that count. Other integer dtypes are refused: the
  kernel has no exact path for them.

The layout arrays are plain operands of ``segment_sum_arrays``, so a jitted
caller passes them as arguments and its program depends on their shapes
only, never on the arcs they hold. They are lane-dense — one
(be/128, 128) tile per edge block — like everything the kernel reads.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import platform as _platform
from repro.kernels.segment_sum.kernel import LANES, row_hits_pallas, segment_sum_pallas

# every integer below 2**24 is exact in float32
EXACT_COUNT_LIMIT = 1 << 24


@dataclasses.dataclass(frozen=True)
class BlockedLayout:
    """Host-precomputed edge order/padding such that every ``be``-edge block
    touches one ``R``-row output block (see kernel.py)."""

    slot_edge: np.ndarray  # (n_blocks, be/128, 128) int32 edge per slot; E = padding
    rows_local: np.ndarray  # (n_blocks, be/128, 128) int32; padding slots: row 0
    block_row: np.ndarray  # (n_blocks,) int32
    R: int
    be: int
    n_rows_pad: int
    max_count: int  # most edges in any one segment


def blocked_layout(
    seg_ids: np.ndarray, n_rows: int, *, R: int = 1024, be: int = 2048
) -> BlockedLayout:
    if R % LANES or be % LANES:
        raise ValueError(f"R={R} and be={be} must be multiples of {LANES}")
    seg_ids = np.asarray(seg_ids)
    E = seg_ids.shape[0]
    max_count = int(np.bincount(seg_ids).max()) if E else 0
    if max_count >= EXACT_COUNT_LIMIT:
        raise ValueError(
            f"a segment holds {max_count} edges; the kernel's f32 "
            f"accumulator counts exactly only below {EXACT_COUNT_LIMIT}"
        )
    if np.all(seg_ids[1:] >= seg_ids[:-1]):
        # already sorted (a static graph's arc sources): skip the O(E log E)
        # sort, which at 10^8 arcs costs seconds of host time
        order, seg_sorted = np.arange(E), seg_ids
    else:
        order = np.argsort(seg_ids, kind="stable")
        seg_sorted = seg_ids[order]
    n_rb = max((n_rows + R - 1) // R, 1)
    # edges per row block
    rb_of_edge = seg_sorted // R
    counts = np.bincount(rb_of_edge, minlength=n_rb)
    # >= 1 block per row block even when it has no edges: the kernel
    # zero-initializes an output block on first visit, so every block must
    # be visited (found by hypothesis: E=1, n=17 left rows 16.. garbage).
    blocks_per_rb = np.maximum((counts + be - 1) // be, 1)
    # allocate padded slots per row block
    slot_starts = np.concatenate([[0], np.cumsum(blocks_per_rb * be)[:-1]])
    e_starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = slot_starts[rb_of_edge] + (np.arange(E) - e_starts[rb_of_edge])
    e_pad = int((blocks_per_rb * be).sum()) or be
    rows_local = np.zeros(e_pad, np.int32)
    rows_local[slot] = (seg_sorted % R).astype(np.int32)
    slot_edge = np.full(e_pad, E, np.int32)
    slot_edge[slot] = order
    block_row = np.repeat(np.arange(n_rb), blocks_per_rb).astype(np.int32)
    if block_row.size == 0:
        block_row = np.zeros(1, np.int32)
    return BlockedLayout(
        slot_edge=slot_edge.reshape(-1, be // LANES, LANES),
        rows_local=rows_local.reshape(-1, be // LANES, LANES),
        block_row=block_row,
        R=R,
        be=be,
        n_rows_pad=n_rb * R,
        max_count=max_count,
    )


def slot_rows(layout: BlockedLayout) -> np.ndarray:
    """(E_pad,) int32 segment of every slot in the layout's padded slot
    order; a padding slot reads its block's first row, which is < n_rows."""
    return (layout.block_row[:, None, None] * layout.R + layout.rows_local).reshape(-1)


def to_slots(x, slot_edge, fill):
    """Arc-order ``x`` ((E,) or (E, F)) in the layout's padded slot order:
    (E_pad,) or (E_pad, F), ``fill`` on padding slots (slot_edge == E).
    A numpy ``x`` stays on the host; a jax one is one gather."""
    xp = jnp if isinstance(x, jax.Array) else np
    pad = xp.full((1,) + x.shape[1:], fill, x.dtype)
    return xp.concatenate([x, pad])[slot_edge.reshape(-1)]


def segment_sum_arrays(
    vals, slot_edge, rows_local, block_row, *, R: int, n_rows_pad: int, n_rows: int
):
    """Traceable blocked segment sum with the layout passed as arrays.

    vals: (E_pad,) or (E_pad, F) in the layout's padded slot order
    (``to_slots``; zero on padding slots), bool (counted to int32) or
    floating (summed in f32, returned in ``vals.dtype``). It reaches the
    kernel with no gather. Returns (n_rows, F); each feature column is one
    kernel call.
    """
    counting = vals.dtype == jnp.bool_
    if not counting and not jnp.issubdtype(vals.dtype, jnp.floating):
        raise TypeError(f"segment sum takes bool indicators or floats, got {vals.dtype}")
    if vals.ndim == 1:
        vals = vals[:, None]
    dt = jnp.bfloat16 if counting else jnp.float32
    cols = []
    for f in range(vals.shape[1]):
        x = vals[:, f].astype(dt).reshape(slot_edge.shape)
        out = segment_sum_pallas(
            x, rows_local, block_row, n_rows_pad // R, R=R, interpret=_platform.interpret_kernels()
        )
        cols.append(out.reshape(-1)[:n_rows])
    out = jnp.stack(cols, axis=1)
    return out.astype(jnp.int32) if counting else out.astype(vals.dtype)


def row_hits_arrays(est, probe, rows_local, block_row, *, R: int, n_rows_pad: int, n_rows: int):
    """Traceable per-row hit count of the binary-search h-index.

    est: (E_pad,) int32 in the layout's padded slot order, each slot's
    neighbour estimate (zero on padding slots); probe: (n_rows,) int32, one
    per row. Returns (n_rows,) int32: per row r, the slots of r with
    ``est >= probe[r] > 0``. The kernel broadcasts each row's probe to its
    slots in VMEM, exactly for every int32, so no (E_pad,) array of probes
    is gathered or written.
    """
    probe = jnp.pad(probe, (0, n_rows_pad - n_rows)).reshape(n_rows_pad // R, R // LANES, LANES)
    out = row_hits_pallas(
        est.reshape(rows_local.shape),
        rows_local,
        block_row,
        probe,
        R=R,
        interpret=_platform.interpret_kernels(),
    )
    return out.reshape(-1)[:n_rows]


@functools.partial(jax.jit, static_argnames=("R", "n_rows_pad", "n_rows"))
def _run(vals, slot_edge, rows_local, block_row, R, n_rows_pad, n_rows):
    return segment_sum_arrays(
        to_slots(vals, slot_edge, 0),
        slot_edge,
        rows_local,
        block_row,
        R=R,
        n_rows_pad=n_rows_pad,
        n_rows=n_rows,
    )


def segment_sum_blocked(vals, layout: BlockedLayout, n_rows: int):
    """vals: (E,) or (E, F) in ORIGINAL edge order. Returns (n_rows, F)."""
    return _run(
        jnp.asarray(vals),
        jnp.asarray(layout.slot_edge),
        jnp.asarray(layout.rows_local),
        jnp.asarray(layout.block_row),
        layout.R,
        layout.n_rows_pad,
        n_rows,
    )
