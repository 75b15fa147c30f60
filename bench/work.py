"""Work of each Pallas kernel call, from the operands the engine hands to
the kernel's ops wrapper — not from the kernel's own padded layout, so a
later re-implementation of a kernel is read against the same work.

Both kernels are counted by HBM bytes alone, against the chip's HBM
bandwidth (``bench.device.PEAKS``): they do 0/1 compares, row sums and a
one-hot matrix product whose only purpose is a scatter-add, none of which
is arithmetic the published matrix-unit peaks describe, so no compute
bound is counted.

``KernelShapes.install`` wraps the two wrappers as the dispatch layer
calls them (``repro.core.dispatch.hindex_rows`` and
``repro.core.dispatch.segment_sum_arrays``). The wrap runs only while jax
traces a program, records the operand shapes and calls through unchanged,
so the compiled programs are the same.
"""

from __future__ import annotations

import numpy as np


def hindex_bytes(rows: int, width: int, itemsize: int = 4) -> int:
    """h-index over an (R, W) ELL bucket: reads the R x W neighbour
    estimates and the R own estimates, writes R results."""
    return itemsize * rows * width + itemsize * rows + 4 * rows


def segsum_bytes(slots: int, mask_itemsize: int, segments: int, id_itemsize: int = 4) -> int:
    """Segment sum of an E-slot mask into N segments: reads the mask and
    the slots' segment ids at their dtypes, writes N int32 sums."""
    return slots * (mask_itemsize + id_itemsize) + 4 * segments


class KernelShapes:
    """Operand shapes each kernel wrapper was traced with, keyed the way
    the trace identifies a call: the h-index by its bucket width, the
    segment sum by the number of edge blocks of its layout."""

    def __init__(self):
        self.hindex: dict = {}  # width -> set of (rows, width, itemsize)
        self.segsum: dict = {}  # edge blocks -> set of (slots, itemsize, segments)

    def install(self):
        from repro.core import dispatch

        orig_h, orig_s = dispatch.hindex_rows, dispatch.segment_sum_arrays

        def hindex_rows(nbr_est, est_u, n_iters):
            rows, width = nbr_est.shape
            self.hindex.setdefault(width, set()).add((rows, width, np.dtype(nbr_est.dtype).itemsize))
            return orig_h(nbr_est, est_u, n_iters=n_iters)

        def segment_sum_arrays(vals, slot_edge, rows_local, block_row, **kw):
            cols = 1 if vals.ndim == 1 else vals.shape[1]
            rec = (vals.shape[0], np.dtype(vals.dtype).itemsize, kw["n_rows"])
            for _ in range(cols):
                self.segsum.setdefault(slot_edge.shape[0], set()).add(rec)
            return orig_s(vals, slot_edge, rows_local, block_row, **kw)

        dispatch.hindex_rows = hindex_rows
        dispatch.segment_sum_arrays = segment_sum_arrays

        def uninstall():
            dispatch.hindex_rows, dispatch.segment_sum_arrays = orig_h, orig_s

        return uninstall

    def event_bytes(self, ev) -> int | None:
        """HBM bytes of one traced kernel event, or None when its wrapper
        call was not seen or is ambiguous."""
        if ev.kernel == "hindex":
            recs = self.hindex.get(ev.operands[0][1][1]) if ev.operands else None
            if not recs or len(recs) != 1:
                return None
            (rows, width, itemsize), = recs
            return hindex_bytes(rows, width, itemsize)
        recs = self.segsum.get(ev.operands[0][1][0]) if ev.operands else None
        if not recs or len(recs) != 1:
            return None
        (slots, itemsize, segments), = recs
        return segsum_bytes(slots, itemsize, segments)


def roofline_pct(events: list, shapes: KernelShapes, kernel: str, bytes_per_s: float) -> float | None:
    """Least time over kernel time, in %, over every event of ``kernel``;
    None when the trace holds none or one cannot be sized."""
    evs = [e for e in events if e.kernel == kernel]
    if not evs:
        return None
    total = 0
    for e in evs:
        b = shapes.event_bytes(e)
        if b is None:
            return None
        total += b
    seconds = sum(e.seconds for e in evs)
    return 100.0 * (total / bytes_per_s) / seconds if seconds > 0 else None
