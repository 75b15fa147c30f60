"""Pallas TPU fused embedding-bag: gather + in-register reduce.

DIN's hot path (kernel_taxonomy §RecSys): (B, L) item-id bags against a
(V, D) table. The XLA path materializes the (B, L, D) gathered tensor in
HBM before reducing; this kernel keeps the accumulator for one bag tile in
VMEM and DMA-gathers one row at a time from the HBM-resident table (the
indices are scalar-prefetched so the gather addresses are known to the DMA
engine ahead of the loop).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bag_kernel(idx_ref, table_ref, out_ref, *, L: int, bb: int):
    i = pl.program_id(0)
    acc = jnp.zeros(out_ref.shape, jnp.float32)  # (bb, D)

    def body(j, acc):
        def row(b, acc):
            ix = idx_ref[i * bb + b, j]
            valid = ix >= 0
            r = table_ref[pl.ds(jnp.maximum(ix, 0), 1), :]  # (1, D)
            return acc.at[b].add(jnp.where(valid, r[0], 0.0).astype(jnp.float32))

        return jax.lax.fori_loop(0, bb, row, acc)

    acc = jax.lax.fori_loop(0, L, body, acc)
    out_ref[...] = acc.astype(out_ref.dtype)


def embedding_bag_pallas(table, indices, *, bb: int, interpret: bool):
    """table: (V, D); indices: (B, L) int32 (−1 = padding) -> (B, D) sums."""
    V, D = table.shape
    B, L = indices.shape
    grid = (B // bb,)
    return pl.pallas_call(
        functools.partial(_bag_kernel, L=L, bb=bb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((bb, D), lambda i, idx: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, D), table.dtype),
        interpret=interpret,
    )(indices, table)
