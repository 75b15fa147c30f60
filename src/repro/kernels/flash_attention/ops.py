"""Jit'd wrapper: shape plumbing (B,H grouping, GQA), block-size selection,
padding, interpret mode on the CPU backend."""

from __future__ import annotations

import functools

import jax

from repro import platform as _platform
from repro.kernels.flash_attention.kernel import flash_attention_pallas


def _pick_blocks(Sq: int, Sk: int, d: int) -> tuple[int, int]:
    bq = min(512, Sq)
    while Sq % bq:
        bq //= 2
    bk = min(512, Sk)
    while Sk % bk:
        bk //= 2
    return max(bq, 1), max(bk, 1)


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None):
    """q: (B, Sq, Hq, d), k/v: (B, Sk, Hkv, d) -> (B, Sq, Hq, d).

    Drop-in for the XLA chunked path in models/transformer (same masking
    semantics: causal + optional sliding window over absolute positions).
    """
    B, Sq, Hq, d = q.shape
    _, Sk, Hkv, _ = k.shape
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, d)
    bq, bk = _pick_blocks(Sq, Sk, d)
    out = flash_attention_pallas(
        qf,
        kf,
        vf,
        causal=causal,
        window=window,
        bq=bq,
        bk=bk,
        interpret=_platform.interpret_kernels(),
    )
    return out.reshape(B, Hq, Sq, d).transpose(0, 2, 1, 3)
