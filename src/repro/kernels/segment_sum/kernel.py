"""Pallas TPU segment-sum over row-block-grouped sorted COO.

The scatter in ``jax.ops.segment_sum`` is the message-aggregation hot spot of
the k-core engine. TPUs have no efficient scatter; the TPU-native
formulation is a ONE-HOT MATMUL per edge block on the MXU, accumulated into
a VMEM-resident output row block.

Every operand is lane-dense: an edge block of ``be`` edges arrives as a
(be/128, 128) tile of values and one of local rows, and the output row
block of ``R`` rows is an (R/128, 128) tile holding row r at (r // 128,
r % 128). A column layout — (E, 1) arrays — would pad every element to 128
lanes in HBM. The tiles are the trailing dims of 3-D arrays, so any ``R``
and ``be`` that are multiples of 128 compile; multiples of 1024 fill whole
(8, 128) tiles (the defaults, R=1024 and be=2048, do). Each 128-edge
sublane s contributes

    out[h, l] += sum_e [row_e // 128 == h] * [row_e % 128 == l] * v_e

as one (R/128, 128) x (128, 128)^T matmul of two one-hots built from the
edges on the lane axis. The MXU has no int32 matmul (Mosaic refuses
``vector<...xi32>`` operands): counts of 0/1 indicators go through as bf16
with f32 accumulation — exact while a segment sums fewer than 2^24 of them
(ops.py enforces that bound on the layout) — and float sums as f32 at
HIGHEST precision.

Layout contract (built by ops.blocked_layout): edges are sorted by segment
and PADDED so each edge block touches exactly one output row block;
``block_row[i]`` (scalar-prefetched — the out BlockSpec index map reads it)
names that row block. Sorted edges mean each out block is visited by
consecutive grid steps, so the accumulate-in-VMEM pattern is safe on TPU's
sequential grid.

``row_hits`` is the binary-search h-index's hit count on the same layout:
it takes each row's probe as an (R/128, 128) tile of its row block, moves
it to the row's edges with the transpose of the same two one-hots (a
matmul that picks column r % 128, a select of sublane r // 128), tests
``est >= probe > 0`` per edge and counts the hits per row as above. An
int32 probe crosses the MXU as its four bytes, each an integer below 256
and so exact in bf16; the shifts put them back together.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _seg_kernel(block_row_ref, vals_ref, rows_ref, out_ref):
    i = pl.program_id(0)
    first = jnp.logical_or(i == 0, block_row_ref[jnp.maximum(i - 1, 0)] != block_row_ref[i])

    @pl.when(first)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    vals = vals_ref[...]  # (be/128, 128) f32 or bf16, edges on lanes
    rows = rows_ref[...]  # (be/128, 128) int32 local row in [0, R)
    # bf16 values are 0/1 counts: one bf16 MXU pass is exact for them;
    # f32 sums take the multi-pass HIGHEST precision
    # (one-hots are built in f32: Mosaic cannot select bf16 under a mask)
    dt = vals.dtype
    precision = jax.lax.Precision.HIGHEST if dt == jnp.float32 else None
    vals = vals.astype(jnp.float32)
    hi_iota = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    lo_iota = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for s in range(vals.shape[0]):
        r = rows[s : s + 1, :]
        hi = (hi_iota == r // LANES).astype(jnp.float32).astype(dt)  # (R/128, 128 edges)
        lo = jnp.where(lo_iota == r % LANES, vals[s : s + 1, :], 0.0).astype(dt)  # (128, 128)
        acc += jax.lax.dot_general(
            hi,
            lo,
            (((1,), (1,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32,
        )
    out_ref[...] += acc


def segment_sum_pallas(vals, rows_local, block_row, n_blocks_out: int, *, R: int, interpret: bool):
    """vals: (n_edge_blocks, be/128, 128) f32 (or bf16 0/1 counts) and
    rows_local: the same shape in int32, edges in padded slot order; block_row: (n_edge_blocks,)
    int32 out-block id per edge block. Returns (n_blocks_out, R/128, 128)
    f32: the sum of row r of out block b at [b, r // 128, r % 128]."""
    sub = vals.shape[1]  # sublanes per edge block
    return pl.pallas_call(
        _seg_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(block_row.shape[0],),
            in_specs=[
                pl.BlockSpec((None, sub, LANES), lambda i, br: (i, 0, 0)),
                pl.BlockSpec((None, sub, LANES), lambda i, br: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, R // LANES, LANES), lambda i, br: (br[i], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_blocks_out, R // LANES, LANES), jnp.float32),
        interpret=interpret,
        name="segment_sum",
    )(block_row, vals, rows_local)


# bytes of an int32 probe, each broadcast exactly as one bf16 integer < 256
PROBE_BYTES = 4


def _row_hits_kernel(block_row_ref, est_ref, rows_ref, probe_ref, out_ref):
    i = pl.program_id(0)
    first = jnp.logical_or(i == 0, block_row_ref[jnp.maximum(i - 1, 0)] != block_row_ref[i])

    @pl.when(first)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    est = est_ref[...]  # (be/128, 128) int32 neighbour estimate, edges on lanes
    rows = rows_ref[...]  # (be/128, 128) int32 local row in [0, R)
    probe = probe_ref[...]  # (R/128, 128) int32: row r's probe at (r // 128, r % 128)
    n_hi = probe.shape[0]
    # the probe's bytes, stacked on sublanes: every one is an integer below
    # 256, exact in bf16, so one bf16 pass broadcasts the whole int32
    # (byte b = 3 keeps the sign bits; the shifts below wrap them back)
    pieces = jnp.concatenate(
        [((probe >> (8 * b)) & 0xFF).astype(jnp.float32) for b in range(PROBE_BYTES)], axis=0
    ).astype(jnp.bfloat16)  # (PROBE_BYTES * R/128, 128 rows)
    hi_iota = jax.lax.broadcasted_iota(jnp.int32, (n_hi, LANES), 0)
    lo_iota = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for s in range(est.shape[0]):
        r = rows[s : s + 1, :]
        hi = hi_iota == r // LANES  # (R/128, 128 edges)
        lo = (lo_iota == r % LANES).astype(jnp.float32)  # (128 rows, 128 edges)
        # every edge's row probe, byte by byte: pieces[:, r % 128]
        sel = jax.lax.dot_general(
            pieces,
            lo.astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)  # (PROBE_BYTES * R/128, 128 edges)
        by_hi = sel[:n_hi]
        for b in range(1, PROBE_BYTES):
            by_hi = by_hi + (sel[b * n_hi : (b + 1) * n_hi] << (8 * b))
        mid = jnp.sum(jnp.where(hi, by_hi, 0), axis=0, keepdims=True)  # (1, 128 edges)
        hit = jnp.logical_and(est[s : s + 1, :] >= mid, mid > 0).astype(jnp.float32)
        acc += jax.lax.dot_general(
            hi.astype(jnp.float32).astype(jnp.bfloat16),
            (lo * hit).astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    out_ref[...] += acc.astype(jnp.int32)


def row_hits_pallas(est, rows_local, block_row, probe, *, R: int, interpret: bool):
    """est and rows_local: (n_edge_blocks, be/128, 128) int32 in padded slot
    order (a slot's neighbour estimate, and its local row); block_row:
    (n_edge_blocks,) int32 out-block id per edge block; probe:
    (n_blocks_out, R/128, 128) int32, row r of out block b at [b, r // 128,
    r % 128]. Returns the same shape in int32: per row, the slots with
    ``est >= probe > 0``."""
    sub = est.shape[1]
    tile = pl.BlockSpec((None, R // LANES, LANES), lambda i, br: (br[i], 0, 0))
    return pl.pallas_call(
        _row_hits_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(block_row.shape[0],),
            in_specs=[
                pl.BlockSpec((None, sub, LANES), lambda i, br: (i, 0, 0)),
                pl.BlockSpec((None, sub, LANES), lambda i, br: (i, 0, 0)),
                tile,
            ],
            out_specs=tile,
        ),
        out_shape=jax.ShapeDtypeStruct(probe.shape, jnp.int32),
        interpret=interpret,
        name="row_hits",
    )(block_row, est, rows_local, probe)
