"""flash_attention kernel package."""

from repro.kernels.flash_attention.ops import flash_attention

__all__ = ["flash_attention"]
