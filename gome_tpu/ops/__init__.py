"""Custom TPU kernels (Pallas) for the matching hot path."""

from .pallas_match import (
    blockable_rows,
    default_block_s,
    kernel_plan,
    pallas_available,
    pallas_batch_step,
)

__all__ = [
    "blockable_rows",
    "default_block_s",
    "kernel_plan",
    "pallas_available",
    "pallas_batch_step",
]
