"""TPU kernels (pallas) for the hot ops, each with its fused-XLA reference
(the kernel on ``tpu``, the reference on every other backend)."""

from .flash_attention import attention, flash_attention, reference_attention

__all__ = ["attention", "flash_attention", "reference_attention"]
