"""Jit'd wrapper for the fused embedding-bag kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import platform as _platform
from repro.kernels.embedding_bag.kernel import embedding_bag_pallas


@functools.partial(jax.jit, static_argnames=())
def embedding_bag_fused(table, indices):
    """table (V, D), indices (B, L) int32 (−1 pad) -> (B, D) sum-bags."""
    B, L = indices.shape
    bb = 8
    pad = (-B) % bb
    if pad:
        indices = jnp.pad(indices, ((0, pad), (0, 0)), constant_values=-1)
    out = embedding_bag_pallas(table, indices, bb=bb, interpret=_platform.interpret_kernels())
    return out[:B]
