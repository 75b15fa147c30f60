"""segment_sum kernel package: blocked one-hot-matmul segment sum, and the
binary-search h-index's per-row hit count on the same layout."""

from repro.kernels.segment_sum.ops import (
    blocked_layout,
    row_hits_arrays,
    segment_sum_arrays,
    segment_sum_blocked,
)

__all__ = ["blocked_layout", "row_hits_arrays", "segment_sum_arrays", "segment_sum_blocked"]
