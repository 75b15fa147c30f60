"""embedding_bag kernel package: fused gather + reduce."""

from repro.kernels.embedding_bag.ops import embedding_bag_fused

__all__ = ["embedding_bag_fused"]
