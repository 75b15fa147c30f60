"""kcore_hindex kernel package: rowwise clipped h-index over ELL tiles."""

from repro.kernels.kcore_hindex.ops import hindex_rows

__all__ = ["hindex_rows"]
