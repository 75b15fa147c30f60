"""segment_sum kernel package: blocked one-hot-matmul segment sum."""

from repro.kernels.segment_sum.ops import blocked_layout, segment_sum_arrays, segment_sum_blocked

__all__ = ["blocked_layout", "segment_sum_arrays", "segment_sum_blocked"]
